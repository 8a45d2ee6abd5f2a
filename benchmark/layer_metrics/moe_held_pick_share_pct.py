"""Share of the router's picks that this chip computes: 100 x sum of
``moe_held_assignments`` / sum of ``moe_assignments`` over the window's
ticks (flight ring; the step program of a model whose expert layer is told
which experts it holds returns both, docs/observability.md).  Held experts
/ all experts when the router is even: 50 for 36 of 72.  None where no tick
carries the counters (another model, or a program from before them)."""


def read(run, params):
    ticks = [t for t in run["window"]["ticks"]
             if t.get("moe_held_assignments") is not None
             and t.get("moe_assignments")]
    if not ticks or run["window"]["ring_full"]:
        return None
    return 100.0 * sum(t["moe_held_assignments"] for t in ticks) \
        / sum(t["moe_assignments"] for t in ticks)
