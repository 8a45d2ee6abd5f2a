"""How unevenly a tick's picks fell over the experts HELD here: the mean
over the window's ticks of ``moe_max_load`` (the most assignments any one
held expert got in one layer and one pass) / (``moe_held_assignments`` /
the held experts of all layers / the tick's passes, ``ssm_passes``), from
the flight ring (docs/observability.md); the family counts the held experts.
A tick that carries a prompt chunk beside decode rows passes the model twice,
a step of several tokens once a token.  1 is an even load.  None where no
tick carries the counters."""

from statistics import mean

from harness import cells


def read(run, params):
    ticks = [t for t in run["window"]["ticks"]
             if t.get("moe_held_assignments")]
    if not ticks or run["window"]["ring_full"]:
        return None
    slots = cells.family(run["cfg"]).counts.held_expert_slots(run["cfg"])
    return mean(t["moe_max_load"]
                / (t["moe_held_assignments"] / slots / t["ssm_passes"])
                for t in ticks)
