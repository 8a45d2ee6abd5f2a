"""How much of a tick's memory traffic is the latent cache that the MLA
layers read: 100 x sum of ``latent_kv_bytes`` (the latent rows the tick's
gathers read, decode rows and chunk rows alike: flight ring,
docs/observability.md) / (that + the weight bytes the tick's passes read +
the recurrent state the tick read and wrote, ``ssm_state_bytes``), over the
window's ticks.  Weights from the cell's family (counts.py), as
``ssm_state_bytes_share_pct`` reads them: a tick passes the model
``ssm_passes`` times, each pass reading everything that is not a routed
expert and the held experts its picks hit.  None where no tick carries the
counter (a model without latent attention, or a program from before it)."""

from harness import cells


def read(run, params):
    ticks = [t for t in run["window"]["ticks"] if t.get("latent_kv_bytes")]
    if not ticks or run["window"]["ring_full"]:
        return None
    cfg = run["cfg"]
    counts = cells.family(cfg).counts
    layers = cells.family(cfg).leaves.n_layers(cfg)
    latent = other = 0
    for t in ticks:
        n = t["ssm_passes"]
        latent += t["latent_kv_bytes"]
        other += t.get("ssm_state_bytes", 0) + n * counts.pass_weight_bytes(
            cfg, t["moe_assignments"] / layers / n)
    return 100.0 * latent / (latent + other)
