"""How unevenly a tick's tokens fell over the experts: the mean over the
window's ticks of ``moe_max_load`` (the most assignments any one expert got
in one layer) / (``moe_assignments`` / the number of experts), from the
flight ring (docs/observability.md).  1 is an even load; the grouped matmul
reads an expert's weights once however many tokens it got, so what an
uneven load costs is the longest group.  None where no tick carries the
counters."""

from statistics import mean


def read(run, params):
    ticks = [t for t in run["window"]["ticks"]
             if t.get("moe_assignments")]
    if not ticks or run["window"]["ring_full"]:
        return None
    experts = run["cfg"]["num_experts"]
    return mean(t["moe_max_load"] / (t["moe_assignments"] / experts)
                for t in ticks)
