"""Share of its roofline the grid seeding kernel reaches: the work the
window's sweeps need (``roofline.crms_grid_work`` from each call's container
counts), at the chip's peaks, over the kernel's device time (its program,
``jit_wrapped`` inside ``grid_seed_chints``; see
``crms_grid.device_ms_per_replan``)."""

PROGRAM = r"^jit_wrapped\("
SPAN = "grid_seed"


def read(ctx):
    calls = ctx.calls.get("crms_grid", [])
    t = ctx.trace.device_ns_within(ctx.events, PROGRAM, SPAN, ctx.lo, ctx.hi)
    if not calls or t <= 0:
        return None
    flops = bytes_ = 0.0
    for args, _ in calls:
        f, b = ctx.roofline.crms_grid_work(args[3])  # (kappa, lam, xbar, n, c, m)
        flops, bytes_ = flops + f, bytes_ + b
    share, _ = ctx.roofline.roofline_share(flops, bytes_, t / 1e9, ctx.device_kind)
    return share
