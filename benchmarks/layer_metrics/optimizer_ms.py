"""Device milliseconds a step spends in ops under ``hvd_optimizer_update``,
printed beside the least time the update's streams allow: Adam reads the
gradient, both moments and the parameter and writes both moments and the
parameter, seven float32 streams.  A reading under that least time is
partial: XLA put part of the update into a fusion that carries another
scope (PERF.md says which).  Device trace."""

from benchmarks.harness import flops

#: float32 streams over every parameter: reads g, m, v, p; writes m, v, p
ADAM_STREAMS = 7


def parameters(cfg: dict, mix: dict) -> int:
    """Every GPT-2 parameter the optimizer updates: what a token multiplies
    and the position table."""
    positions = max(int(mix["arrays"][0]["shape"][0]),
                    int(cfg["n_positions"]))
    return flops.gpt2_matmul_params(cfg) + positions * cfg["n_embd"]


def read(run):
    seconds = run.reduced.op_seconds(
        lambda op: "hvd_optimizer_update" in op.tf_op)
    if seconds <= 0:
        return None
    nbytes = ADAM_STREAMS * 4 * parameters(run.cell.cfg, run.cell.mix)
    print(f"optimizer_ms: {nbytes:.4g} bytes a step in {ADAM_STREAMS} "
          f"float32 streams, least {nbytes / run.peak.hbm_bytes * 1e3:.3f} "
          f"ms", flush=True)
    return run.per_step_ms(seconds)
