"""The decode-attention kernel's share of its roofline in the hybrid: the
least time of its work at the chip's peaks over its device time in the
trace.  A call is one shared-block application over the whole batch
(MHA: every query head reads its own K and V): the live K and V of each
live slot at the cache's dtype plus the query and output rows
(``work_hybrid.flash_decode_work``); every served step calls the kernel
once per application.  The kernel's operations are the names the driver
read from the decode executables (``kernel_ops``)."""
from chipbench import trace as TR
from chipbench import work, work_hybrid


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
        f, b = work_hybrid.flash_decode_work(run.cfg, ctxs, kv_bytes=kv)
        least += work.least_time(f, b, run.peak)[0]
    least *= work_hybrid.n_apps(run.cfg)
    return 100.0 * least / (t_kernel * 1e-9)
