"""Peak share of the KV pool's blocks in use over the window's ticks."""


def read(run, params):
    ticks = [t for t in run["window"]["ticks"] if t.get("n_blocks")]
    if not ticks:
        return None
    return 100.0 * max(t["used_blocks"] / t["n_blocks"] for t in ticks)
