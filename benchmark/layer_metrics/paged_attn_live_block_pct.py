"""Share of the block tables that held a decode row's data: 100 x sum of
``attn_live_blocks`` / sum of ``attn_table_blocks`` over the window's ticks
(flight ring; a paged engine books both from its host state where the
tick's record is written, docs/observability.md).  ``attn_live_blocks`` is
the blocks the decode rows' next step reads (``pos // block_size + 1`` a
row), ``attn_table_blocks`` the slots x table-width blocks a grid over the
whole table visits: what a fused paged-attention kernel that stops at each
row's frontier skips is 100 minus this.  A property of the traffic, not of
the kernel.  None where no tick carries the counters (a program from
before them, an engine that is not paged) or where the ring wrapped."""


def read(run, params):
    ticks = [t for t in run["window"]["ticks"] if "attn_table_blocks" in t]
    table = sum(t["attn_table_blocks"] for t in ticks)
    if not table or run["window"]["ring_full"]:
        return None
    return 100.0 * sum(t["attn_live_blocks"] for t in ticks) / table
