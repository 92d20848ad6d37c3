"""The hybrid's whole decode step's share of the chip's peak: operations
of the tokens decoded in the traced window (every Mamba-2 layer's
projections and recurrence, each shared-block application's projections,
MLP, adapter, linear and attention over each live session's context, the
tied LM head; ``work_hybrid``) over (traced window x bf16 peak).  The
bf16 peak because the served float32 matmuls run at the TPU's default
precision, one bf16 pass each."""
from chipbench import work_hybrid


def read(run):
    steps = getattr(run.driver, "step_ctxs", None)
    if run.trace is None or not run.trace.ops or not steps or run.peak is None:
        return None
    lo, hi = run.trace.window()
    flops = sum(work_hybrid.decode_step_flops(run.cfg, c) for c in steps)
    return 100.0 * flops / ((hi - lo) * 1e-9 * run.peak["bf16_flops_per_s"]
                            * run.trace.n_devices)
