"""Share of its roofline that one ``allocate`` solve reaches: the least
time the chip could take for the solve's operations and bytes (counted
from nnz(R), F and L by ``benchlib.workcount``; the bytes bound it) over
the device time of the solve."""
from benchlib import tracing
from benchlib.peaks import peaks
from benchlib.workcount import allocate_work, roofline_s


def read(ctx):
    tr, work = ctx.get("trace"), ctx.get("work")
    if not tr or not work:
        return None
    secs, runs = tracing.module_seconds(tr, r"^jit_allocate$")
    if not runs or secs <= 0:
        return None
    t_min, _bound = roofline_s(allocate_work(**work),
                               peaks(ctx["device_kind"]))
    return t_min / (secs / runs) * 100.0
