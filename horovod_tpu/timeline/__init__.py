from .timeline import Timeline, timeline  # noqa: F401


def __getattr__(name):
    # lazy: merge/replay pull analysis-side deps (and recorder pulls
    # jax) that the hot-path timeline must not import at package load
    if name == "replay":
        import importlib

        return importlib.import_module(".replay", __name__)
    if name in ("Recorder", "TimelineHook"):
        from . import recorder

        return getattr(recorder, name)
    raise AttributeError(name)
