"""The program's host spans on the profiler's clock: a few steps of the toy
GPT model through ``ShardedLoader`` and ``make_train_step`` under
``jax.profiler`` on the CPU mesh, inside the loop's ``bench_window``, read
back with the benchmark's own readers.  Counts, names, nesting and
arguments only: no duration is compared with anything."""

import jax
import pytest

import benchmark_tiny
from benchmarks.harness import host_spans, loop, traffic, xplane

STEPS = 4


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """``(RawTrace, [HostSpan])`` of ``STEPS`` steps that start with the
    loader's second pass."""
    import horovod_tpu as hvd
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.configs import gpt2_small

    cfg, mix = benchmark_tiny.GPT_TINY, benchmark_tiny.SEQ_TINY
    trace_dir = str(tmp_path_factory.mktemp("host_spans"))
    hvd.shutdown()
    hvd.init(devices=jax.devices("cpu")[:1])
    try:
        prog = gpt2_small.program(cfg, mix)
        state = init_train_state(prog["model"], prog["optimizer"],
                                 prog["sample"])
        step = make_train_step(
            apply_fn=prog["apply_fn"], loss_fn=prog["loss_fn"],
            optimizer=prog["optimizer"], loss_fetch_steps=1)
        feed = traffic.batches(mix, traffic.dataset(mix, cfg, 1, 7), 7,
                               loop.annotate)
        state, loss = step(state, *prog["xy"](next(feed)))  # compiles
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with loop.annotate("window"):
                for _ in range(STEPS):
                    with loop.annotate("next_batch"):
                        arrays = next(feed)
                    with loop.annotate("dispatch"):
                        state, loss = step(state, *prog["xy"](arrays))
                loss.block_until_ready()
        finally:
            jax.profiler.stop_trace()
    finally:
        hvd.shutdown()
    path = xplane.find(trace_dir)
    return xplane.read(path), host_spans.read(path)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_one_hvd_step_a_step_with_consecutive_numbers(profiled):
    _, spans = profiled
    steps = _named(spans, "hvd_step")
    assert len(steps) == STEPS
    numbers = [int(s.args["step_num"]) for s in steps]
    # the warm step before the trace was step 1
    assert numbers == list(range(2, 2 + STEPS))
    assert len({s.thread for s in steps}) == 1


@pytest.mark.parametrize("child", ["hvd_preflight", "hvd_call",
                                   "hvd_guard", "hvd_loss_fetch"])
def test_the_spans_of_a_step_lie_inside_it_and_share_its_number(profiled,
                                                                child):
    _, spans = profiled
    steps = {int(s.args["step_num"]): s for s in _named(spans, "hvd_step")}
    children = _named(spans, child)
    assert len(children) == STEPS
    for c in children:
        assert c.inside(steps[int(c.args["step_num"])])
    if child == "hvd_loss_fetch":
        # the trailing fetch reads the loss of the step before
        assert [int(c.args["fetched_step"]) for c in children] == [
            int(c.args["step_num"]) - 1 for c in children]


def test_loader_spans_come_from_the_producer_thread(profiled):
    _, spans = profiled
    loop_thread = _named(spans, "hvd_step")[0].thread
    waits = _named(spans, "hvd_loader_wait")
    assert len(waits) >= STEPS
    assert {s.thread for s in waits} == {loop_thread}
    for kind in ("hvd_loader_host_batch", "hvd_loader_h2d"):
        made = _named(spans, kind)
        assert made, kind
        assert loop_thread not in {s.thread for s in made}
        for s in made:
            assert int(s.args["epoch"]) >= 1 and int(s.args["batch"]) >= 0
    # a batch is assembled, then placed, under one (epoch, batch)
    assembled = {(int(s.args["epoch"]), int(s.args["batch"])): s
                 for s in _named(spans, "hvd_loader_host_batch")}
    for s in _named(spans, "hvd_loader_h2d"):
        key = (int(s.args["epoch"]), int(s.args["batch"]))
        if key in assembled:
            assert assembled[key].end_s <= s.start_s
    # the loop's waits are numbered in the order it took the batches
    keys = [(int(s.args["epoch"]), int(s.args["batch"])) for s in waits]
    assert keys == sorted(keys) and len(set(keys)) == len(keys)


def test_program_spans_share_the_loops_clock_and_window(profiled):
    """``harness.xplane.read`` finds the program's spans beside the loop's
    own, all of them inside the one ``bench_window``."""
    raw, spans = profiled
    events = [e for line in raw.planes["/host:CPU"].values() for e in line]
    window = [e for e in events if e.name == "bench_window"]
    assert len(window) == 1
    lo, hi = window[0].start_s, window[0].start_s + window[0].dur_s
    mine = [e for e in events if e.name.startswith("hvd_")]
    assert {e.name for e in mine} >= {
        "hvd_step", "hvd_preflight", "hvd_call", "hvd_guard",
        "hvd_loss_fetch", "hvd_loader_wait"}
    for s in _named(spans, "hvd_step") + _named(spans, "hvd_loader_wait"):
        assert lo <= s.start_s and s.end_s <= hi
    # each call into the step sits inside the loop's own dispatch span
    dispatch = [(e.start_s, e.start_s + e.dur_s) for e in events
                if e.name == "bench_dispatch"]
    for s in _named(spans, "hvd_step"):
        assert any(a <= s.start_s and s.end_s <= b for a, b in dispatch)


def test_no_step_of_the_window_compiled(profiled):
    _, spans = profiled
    assert not _named(spans, "hvd_rebuild")
