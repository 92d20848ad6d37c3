"""The decode-attention kernel's share of its roofline: the least time
its work needs at the chip's peaks over its device time in the trace.
The work of a call is counted from the configuration: the live K and V
of every live slot at the cache's own dtype plus the query and output
rows, and the score and value operations; every served step calls the
kernel once per layer.  The kernel's operations are found by the names
the driver read from the decode executables (``kernel_ops``: the Pallas
calls whose kernel is ``flash_decode_attention``)."""
from chipbench import trace as TR
from chipbench import work


def read(run):
    steps = getattr(run.driver, "step_ctxs", None)
    if run.trace is None or not run.trace.ops or not steps or run.peak is None:
        return None
    lo, hi = run.trace.window()
    names = getattr(run.driver, "kernel_ops", set())
    t_kernel, n = TR.op_time_ns(run.trace.ops, names.__contains__, lo, hi)
    if not n:
        return None
    kv = run.driver.kv_itemsize
    least = 0.0
    for ctxs in steps:
        f, b = work.flash_decode_work(run.cfg, ctxs, kv_bytes=kv)
        least += work.least_time(f, b, run.peak)[0]
    least *= run.cfg["num_hidden_layers"]
    return 100.0 * least / (t_kernel * 1e-9)
