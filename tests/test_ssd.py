"""The chunked state-space scan (``ops/ssd.py``) against the recurrence it
is defined by, float32 to 1e-5 on values and on every gradient: lengths
that are and are not whole chunks, one chunk, one token a chunk; groups of
heads; a decay of zero and a large step; causality; bfloat16 operands
inside their band; what the call keeps by name and that a checkpoint which
saves the names leaves nothing under the scan's scope to make again."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd as ssd_ops
from horovod_tpu.ops.ssd import ssd, ssd_recurrence

B, H, P, G, N = 2, 4, 8, 2, 16


def _operands(rng, seq, dtype=jnp.float32, heads=H, groups=G):
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x = mk(B, seq, heads, P).astype(dtype)
    dt = jax.nn.softplus(mk(B, seq, heads) - 1.0)
    rate = -jnp.exp(0.5 * mk(heads))
    b, c = (mk(B, seq, groups, N).astype(dtype) for _ in range(2))
    return x, dt, rate, b, c, mk(heads)


def _close(got, want, tol=1e-5):
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) \
        <= tol * scale


# a multiple of the chunk, not one (padded with rows of dt = 0 and cut),
# shorter than a chunk (one chunk), a token a chunk (no algebra inside)
@pytest.mark.parametrize("seq,chunk", [(64, 16), (50, 16), (37, 8),
                                       (12, 128), (9, 1)])
def test_values_and_every_gradient_match_the_recurrence(rng, seq, chunk):
    args = _operands(rng, seq)
    weight = jnp.asarray(rng.normal(size=(B, seq, H, P)), jnp.float32)
    _close(ssd(*args, chunk=chunk), ssd_recurrence(*args))
    got = jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=chunk) * weight),
                   argnums=range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(ssd_recurrence(*a) * weight),
                    argnums=range(6))(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        _close(g, w)


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_a_group_serves_consecutive_heads(rng, groups):
    """Group ``g`` serves heads ``g r .. g r + r - 1``: the same as B and C
    repeated to every head."""
    x, dt, rate, b, c, skip = _operands(rng, 40, groups=groups)
    r = H // groups
    _close(ssd(x, dt, rate, b, c, skip, chunk=16),
           ssd(x, dt, rate, jnp.repeat(b, r, axis=2),
               jnp.repeat(c, r, axis=2), skip, chunk=16))
    _close(ssd(x, dt, rate, b, c, skip, chunk=16),
           ssd_recurrence(x, dt, rate, b, c, skip))


def test_a_step_of_zero_neither_decays_nor_writes_and_a_large_one_forgets(
        rng):
    x, dt, rate, b, c, skip = _operands(rng, 48)
    # dt = 0 from row 20 on: the state stands still, y = h_19 C_t + D x_t
    still = dt.at[:, 20:].set(0.0)
    y = ssd(x, still, rate, b, c, skip, chunk=16)
    _close(y, ssd_recurrence(x, still, rate, b, c, skip))
    assert np.isfinite(np.asarray(y)).all()
    # a rate of zero keeps everything: y_t = sum_j dt_j (C_t . B_j) x_j
    keep = jnp.zeros_like(rate)
    _close(ssd(x, dt, keep, b, c, skip, chunk=16),
           ssd_recurrence(x, dt, keep, b, c, skip))
    # a step of 60 under a rate of -1 forgets all that came before it
    # (exp(-60) is 1e-26 of it), no NaN, and what follows row 30 is what a
    # sequence that starts at row 30 gives; to 1e-4 and not 1e-5: the
    # float32 running sum of the steps holds 60, so a later row's decay is
    # resolved to 60 x 2 ** -24 = 4e-6 of itself
    big = dt.at[:, 30].set(60.0)
    y = ssd(x, big, -jnp.ones_like(rate), b, c, skip, chunk=16)
    assert np.isfinite(np.asarray(y)).all()
    _close(y, ssd_recurrence(x, big, -jnp.ones_like(rate), b, c, skip),
           tol=1e-4)
    fresh = ssd(x[:, 30:], big[:, 30:], -jnp.ones_like(rate), b[:, 30:],
                c[:, 30:], skip, chunk=16)
    _close(y[:, 30:], fresh, tol=1e-4)
    # one of 1e4 is an exact zero of the state and still no NaN
    big = dt.at[:, 30].set(1e4)
    assert np.isfinite(np.asarray(ssd(
        x, big, -jnp.ones_like(rate), b, c, skip, chunk=16))).all()
    grads = jax.grad(lambda dt: jnp.sum(ssd(
        x, dt, -jnp.ones_like(rate), b, c, skip, chunk=16)))(big)
    assert np.isfinite(np.asarray(grads)).all()


@pytest.mark.parametrize("at", [1, 16, 33])
def test_no_later_token_moves_an_earlier_output(rng, at):
    x, dt, rate, b, c, skip = _operands(rng, 48)
    before = np.asarray(ssd(x, dt, rate, b, c, skip, chunk=16))
    after = np.asarray(ssd(
        x.at[:, at:].add(1.0), dt.at[:, at:].mul(2.0), rate,
        b.at[:, at:].add(1.0), c.at[:, at:].add(-1.0), skip, chunk=16))
    np.testing.assert_array_equal(after[:, :at], before[:, :at])
    assert np.abs(after[:, at:] - before[:, at:]).max() > 1e-3


def test_bfloat16_operands_stay_inside_their_band(rng):
    """The products' operands (x, B, C, ``dt x``, the masked scores, a
    chunk's start state) are rounded to bfloat16, 8 bits: each is off by up
    to 2 ** -9 of itself, a sum of a chunk's products by a few times that,
    and the state carried between chunks stays float32, so the error does
    not grow with the sequence: under 2% of the largest output at 256
    tokens as at 64."""
    for seq in (64, 256):
        args = _operands(rng, seq, jnp.bfloat16)
        exact = ssd_recurrence(*(a.astype(jnp.float32) for a in args))
        got = ssd(*args, chunk=16)
        assert got.dtype == jnp.bfloat16
        _close(got, exact, tol=0.02)


def test_shapes_that_do_not_fit_are_refused(rng):
    x, dt, rate, b, c, skip = _operands(rng, 16)
    with pytest.raises(ValueError, match="positive number"):
        ssd(x, dt, rate, b, c, skip, chunk=0)
    with pytest.raises(ValueError, match="whole groups"):
        ssd(x, dt, rate, b[:, :, :1].repeat(3, axis=2),
            c[:, :, :1].repeat(3, axis=2), skip)
    with pytest.raises(ValueError, match="ssd takes"):
        ssd(x, dt[..., :2], rate, b, c, skip)


def test_a_checkpoint_that_saves_the_names_does_not_run_the_scan_again(rng):
    """The forward rule names the output and the operands; under a
    ``jax.checkpoint`` that saves both names the backward pass holds the
    chunk algebra once (the backward rule's own), with neither name the
    second run makes it again, and the gradients are the same."""
    args = _operands(rng, 64)
    project = jnp.asarray(rng.normal(size=(P, P)), jnp.float32)

    def layer(x, *rest):
        # an op before the scan, so that the operand is something to save
        return jnp.sum(ssd(jnp.tanh(x @ project), *rest, chunk=16) ** 2)

    def loops(policy):
        """The loops over chunks the traced gradient holds (JAX drops from
        the second run what the saved names make needless), and the
        gradient."""
        f = jax.value_and_grad(jax.checkpoint(layer, policy=policy))
        return str(jax.make_jaxpr(f)(*args)).count(" scan["), f(*args)[1]

    save = jax.checkpoint_policies.save_only_these_names
    kept, grad_kept = loops(save(ssd_ops.SSD_OUT, ssd_ops.SSD_IN))
    nothing, grad_nothing = loops(save())
    # the forward call's; the backward rule's own two (the chunks' states
    # again, and their transpose); and, with nothing kept, the second run's
    assert (kept, nothing) == (3, 4)
    assert loops(save(ssd_ops.SSD_OUT))[0] == 3
    _close(grad_kept, grad_nothing, tol=1e-6)
    _close(grad_kept, jax.grad(layer)(*args), tol=1e-6)


def test_residual_bytes_count_what_the_names_hold():
    assert ssd_ops.residual_bytes(1, 8192, 64, 64, 2) == 8192 * 4096 * 2
    assert ssd_ops.operand_bytes(1, 8192, 64, 64, 8, 128, 2) \
        == 8192 * ((4096 + 2048) * 2 + 64 * 4)


# ---------------------------------------------------------------------------
# the Pallas kernels (what ``ssd`` takes on a TPU where the shapes tile), in
# interpreter mode: the same cases through ``interpret=True``
# ---------------------------------------------------------------------------


def _counted(monkeypatch):
    """A reader of ``hvd_ssm_scan_chunks_traced_total``: ``{(kernel, path):
    chunks}``."""
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read():
        return {tuple(labels.values()): child.get()
                for labels, child in metrics.SSM_SCAN_CHUNKS.samples()}

    return read


def _gradients(fn, args, weight):
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
        argnums=range(6)))(*args)


# 12 chunks of 16 are three grid steps of CHUNKS_PER_STEP; 150 tokens are
# padded to 192 with rows of dt = 0 and cut; 1, 2 and 8 heads a group (a
# bundle of one head, and of two)
@pytest.mark.parametrize("seq,heads,dtype,tol", [
    (192, 2, jnp.float32, 1e-5), (192, 4, jnp.float32, 1e-5),
    (192, 16, jnp.float32, 1e-5), (150, 2, jnp.float32, 1e-5),
    (150, 4, jnp.float32, 1e-5), (150, 16, jnp.float32, 1e-5),
    (192, 4, jnp.bfloat16, 0.02), (150, 16, jnp.bfloat16, 0.02)])
def test_the_kernels_match_the_recurrence_and_the_xla_form(
        rng, seq, heads, dtype, tol):
    """Values and all six gradients, against the recurrence in float32 and
    against ``_chunked``; bfloat16 operands inside the band of
    ``test_bfloat16_operands_stay_inside_their_band``."""
    args = _operands(rng, seq, dtype, heads=heads)
    exact = tuple(a.astype(jnp.float32) for a in args)
    weight = jnp.asarray(rng.normal(size=(B, seq, heads, P)), jnp.float32)
    kernels = lambda *a: ssd(*a, chunk=16, interpret=True)  # noqa: E731
    got = jax.jit(kernels)(*args)
    assert got.dtype == dtype
    _close(got, jax.jit(ssd_recurrence)(*exact), tol)
    _close(got, jax.jit(lambda *a: ssd(*a, chunk=16))(*args), tol)
    grads = _gradients(kernels, args, weight)
    for g, w, x in zip(grads, _gradients(ssd_recurrence, exact, weight),
                       _gradients(lambda *a: ssd(*a, chunk=16), args,
                                  weight)):
        assert g.shape == x.shape and g.dtype == x.dtype
        _close(g, w, 2 * tol)
        _close(g, x, 2 * tol)


def test_the_kernels_under_a_step_of_zero_and_a_large_one(rng):
    x, dt, rate, b, c, skip = _operands(rng, 64)
    ones = -jnp.ones_like(rate)
    still = dt.at[:, 20:].set(0.0)
    _close(ssd(x, still, rate, b, c, skip, chunk=16, interpret=True),
           ssd_recurrence(x, still, rate, b, c, skip))
    big = dt.at[:, 30].set(60.0)
    y = ssd(x, big, ones, b, c, skip, chunk=16, interpret=True)
    _close(y, ssd_recurrence(x, big, ones, b, c, skip), tol=1e-4)
    # one of 1e4 is an exact zero of the state and still no NaN, forward
    # or backward
    big = dt.at[:, 30].set(1e4)
    grads = jax.grad(lambda dt, x: jnp.sum(ssd(
        x, dt, ones, b, c, skip, chunk=16, interpret=True)),
        argnums=(0, 1))(big, x)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


@pytest.mark.parametrize("at", [1, 16, 33])
def test_no_later_token_moves_an_earlier_output_of_the_kernels(rng, at):
    x, dt, rate, b, c, skip = _operands(rng, 48)
    before = np.asarray(ssd(x, dt, rate, b, c, skip, chunk=16,
                            interpret=True))
    after = np.asarray(ssd(
        x.at[:, at:].add(1.0), dt.at[:, at:].mul(2.0), rate,
        b.at[:, at:].add(1.0), c.at[:, at:].add(-1.0), skip, chunk=16,
        interpret=True))
    np.testing.assert_array_equal(after[:, :at], before[:, :at])
    assert np.abs(after[:, at:] - before[:, at:]).max() > 1e-3


def test_the_launch_is_read_from_the_platform_and_the_shapes(
        rng, monkeypatch):
    """Off a TPU, and on one where the shapes do not tile, ``ssd`` takes
    the XLA form and the counter says so; the kernels take a bundle of
    heads, a state and a chunk that are whole lane tiles."""
    read = _counted(monkeypatch)
    args = _operands(rng, 32)
    before = read()
    jax.jit(jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=16))))(*args)
    delta = {k: v - before.get(k, 0) for k, v in read().items()
             if v != before.get(k, 0)}
    # two chunks of 16 cover 32 tokens; 2 rows x 4 heads
    assert delta == {("fwd", "xla"): 16, ("bwd", "xla"): 16}
    before = read()
    jax.jit(jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=16, interpret=True))))(
        *args)
    delta = {k: v - before.get(k, 0) for k, v in read().items()
             if v != before.get(k, 0)}
    assert delta == {("fwd", "interpret"): 16, ("states", "interpret"): 16,
                     ("bwd", "interpret"): 16}

    def shaped(heads, p, groups, n):
        return (jax.ShapeDtypeStruct((1, 256, heads, p), jnp.bfloat16),
                jax.ShapeDtypeStruct((1, 256, groups, n), jnp.bfloat16))

    monkeypatch.setattr(ssd_ops, "_on_tpu", lambda: True)
    assert ssd_ops._path(*shaped(64, 64, 8, 128), 128, None) == "mosaic"
    assert ssd_ops._path(*shaped(8, 128, 8, 128), 128, None) == "mosaic"
    for case, chunk in ((shaped(8, 64, 8, 128), 128),      # one head of 64
                        (shaped(64, 64, 8, 64), 128),      # half a lane tile
                        (shaped(64, 64, 8, 128), 64),
                        (shaped(4, 8, 2, 16), 16)):
        assert ssd_ops._path(*case, chunk, None) == "xla"
        with pytest.raises(ValueError, match="multiples of 128"):
            ssd_ops._path(*case, chunk, False)
    monkeypatch.setattr(ssd_ops, "_on_tpu", lambda: False)
    assert ssd_ops._path(*shaped(64, 64, 8, 128), 128, None) == "xla"


def test_a_checkpoint_that_saves_the_names_runs_no_kernel_again(rng):
    """As the XLA form: with the output and the operands saved by name the
    traced gradient holds each kernel once (forward; the backward rule's
    states pass and backward kernel), with nothing saved the forward
    kernel a second time; and the rule keeps no state: what a checkpoint
    that saves the names holds is ``residual_bytes`` of output and
    ``operand_bytes`` of operands."""
    args = _operands(rng, 64)
    project = jnp.asarray(rng.normal(size=(P, P)), jnp.float32)

    def layer(x, *rest):
        return jnp.sum(ssd(jnp.tanh(x @ project), *rest, chunk=16,
                           interpret=True) ** 2)

    def kernels(policy):
        f = jax.value_and_grad(jax.checkpoint(layer, policy=policy))
        text = str(jax.make_jaxpr(f)(*args))
        return [text.count(f"name={k}\n") + text.count(f"name={k} ")
                for k in (ssd_ops.FWD_KERNEL, ssd_ops.STATES_KERNEL,
                          ssd_ops.BWD_KERNEL)], f(*args)[1]

    save = jax.checkpoint_policies.save_only_these_names
    kept, grad_kept = kernels(save(ssd_ops.SSD_OUT, ssd_ops.SSD_IN))
    nothing, grad_nothing = kernels(save())
    assert (kept, nothing) == ([1, 1, 1], [2, 1, 1])
    _close(grad_kept, grad_nothing, tol=1e-6)
    _close(grad_kept, jax.grad(layer)(*args), tol=1e-6)
    from jax._src.ad_checkpoint import saved_residuals

    saved = saved_residuals(
        jax.checkpoint(layer, policy=save(ssd_ops.SSD_OUT, ssd_ops.SSD_IN)),
        *args)
    # beside the layer's own arguments and constants: what the names hold
    named = [aval for aval, why in saved if why.startswith("output of")]
    assert sum(a.size * a.dtype.itemsize for a in named) \
        == ssd_ops.residual_bytes(B, 64, H, P, 4) \
        + ssd_ops.operand_bytes(B, 64, H, P, G, N, 4)
