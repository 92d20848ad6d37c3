"""The grouped SSD scan kernel's share of its roofline: the least time of
its work at the chip's peaks over its device time, in the traced
window's decode steps and admissions.  Its operations are the Pallas
calls the driver found in the decode and admission executables
(``ssd_ops``), counted inside the benchmark's ``step`` and ``admit``
spans (a recompute hand-off's calls are left out).  Work is of the
algorithm (``work_hybrid.ssd_scan_work``): per served step, every layer
over the live slots for one token; per admission, every layer over the
prompt's tokens (the padding of the admission bucket is not work)."""
import bisect

from chipbench import trace as TR
from chipbench import work, work_hybrid


def read(run):
    drv = run.driver
    steps = getattr(drv, "step_ctxs", None)
    names = getattr(drv, "ssd_ops", None)
    if run.trace is None or not run.trace.ops or not steps or not names \
            or run.peak is None:
        return None
    lo, hi = run.trace.window()
    spans = TR.union((s, e) for _, s, e in run.trace.spans_named("step")
                     + run.trace.spans_named("admit"))
    starts = [a for a, _ in spans]
    t_kernel = n = 0
    for name, s, e in run.trace.ops:
        if lo <= s <= hi and TR.op_id(name) in names:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s <= spans[i][1]:
                t_kernel, n = t_kernel + (e - s), n + 1
    if not n:
        return None
    least = 0.0
    for ctxs in steps:
        least += work.least_time(
            *work_hybrid.ssd_scan_work(run.cfg, len(ctxs), 1), run.peak)[0]
    for p in getattr(drv, "admit_lens", ()):
        least += work.least_time(
            *work_hybrid.ssd_scan_work(run.cfg, 1, p), run.peak)[0]
    least *= run.cfg["num_hidden_layers"]
    return 100.0 * least / (t_kernel * 1e-9)
