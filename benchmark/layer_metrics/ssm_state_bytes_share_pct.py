"""How much of a tick's memory traffic is the recurrent state: 100 x sum of
``ssm_state_bytes`` (the state a tick read and wrote: flight ring,
docs/observability.md) / (that + the weight bytes the tick's passes read +
the K/V bytes its decode rows read), over the window's ticks.  Weights and
K/V from the cell's family (counts.py): a pass reads everything that is not
a routed expert and the held experts its picks hit; a tick passes the model
``ssm_passes`` times (twice where it carries a chunk beside decode rows, once
a token in a step of several); K/V by whole blocks (``attn_live_blocks``, the
decode rows' tables, once a pass).  None where no tick carries the counter (a model
without state-space layers, or a program from before it)."""

from harness import cells


def read(run, params):
    ticks = [t for t in run["window"]["ticks"]
             if t.get("ssm_state_bytes")]
    if not ticks or run["window"]["ring_full"]:
        return None
    cfg = run["cfg"]
    counts = cells.family(cfg).counts
    layers = cells.family(cfg).leaves.n_layers(cfg)
    block = counts.kv_bytes(cfg, cfg["engine"]["engine_block_size"])
    state = other = 0
    for t in ticks:
        n = t["ssm_passes"]
        decode = n - (1 if t.get("chunks") else 0)
        state += t["ssm_state_bytes"]
        other += n * counts.pass_weight_bytes(
            cfg, t["moe_assignments"] / layers / n) \
            + decode * t.get("attn_live_blocks", 0) * block
    return 100.0 * state / (state + other)
