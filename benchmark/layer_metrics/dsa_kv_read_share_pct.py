"""Share of the decode rows' cached positions whose K/V the attention read:
100 x sum of ``dsa_read_tokens`` / sum of ``dsa_ctx_tokens`` over the
window's ticks (flight ring; the step program of a model with a
sparse-attention indexer returns both with its tokens,
docs/observability.md).  About ``topk`` / the mean decode context when the
decode step gathers the selected positions only, 100 if it reads densely
under a mask.  None where no tick carries the counters (a model without an
indexer, or a program from before them)."""


def read(run, params):
    ticks = [t for t in run["window"]["ticks"]
             if t.get("dsa_ctx_tokens")]
    if not ticks or run["window"]["ring_full"]:
        return None
    return 100.0 * sum(t["dsa_read_tokens"] for t in ticks) \
        / sum(t["dsa_ctx_tokens"] for t in ticks)
