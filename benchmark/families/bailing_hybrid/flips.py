#!/usr/bin/env python3
"""What the bf16 program's expert choices do to the served gap: a check by
hand of the cell's ``correct`` limit, on the chip at the published widths.

    python3 benchmark/families/bailing_hybrid/flips.py --seed <n> \
        [--sequences 2] [--length 2048] [--scale w_out=0.25 ...] [--tiny]

On ``--sequences`` sequences of ``--length`` token ids drawn from the seed,
with the cell's seeded weights:

- the plain float32 reference (``reference.py``) layer by layer, keeping
  which experts it chooses for every token and expert layer, and its fp8
  control (the harness's ``quant="fp8"``);
- the program's uncached forward (``HybridLM.__call__``, bf16, the chunked
  delta rule, the absorbed latent attention), once choosing its experts
  itself and once FORCED to the reference's choice (its own scores give
  the gates).

One JSON line: the share of token-layers whose chosen set differs from the
reference's (``flip_share``, and per layer), and for the program's own
choice, the forced choice and the control the largest and the 99th
percentile of the gap that ``harness/reference.served_gaps`` reads (the
reference's best logit less its logit of the token put first), over every
position.  A gap that the forced program does not show is the choices'.

``--scale leaf=factor`` multiplies a layer leaf's standard deviation (e.g.
``w_out``, the routed experts' down projection): what another seeded scale
would read.  ``--tiny`` runs the configuration's toy sizes on the CPU.
The program's router is reached through ``model.program()``, since
``model.py`` is the family's one file that imports the package.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(os.path.dirname(HERE))
ROOT = os.path.dirname(BENCH_DIR)
CELL = "ling-3.0-flash-l8-ep4.longgen"


def _scaled(cfg: dict, scales: dict) -> dict:
    """``cfg`` whose family leaves have their std multiplied by
    ``scales[name]`` (a new key, so no cache of the old leaves is hit)."""
    if not scales:
        return cfg
    from harness import cells

    leaves = cells.family(cfg).leaves
    if not hasattr(leaves, "_unscaled"):
        leaves._unscaled = leaves.layer_leaves

        def layer_leaves(c, kind):
            out = leaves._unscaled(c, kind)
            for name, f in (c.get("_leaf_scales") or {}).items():
                if name in out:
                    shape, k, std = out[name]
                    out[name] = (shape, k, std * f)
            return out
        leaves.layer_leaves = layer_leaves
    return dict(cfg, _leaf_scales=dict(scales))


def _gaps(ref, choice):
    """The reference's best logit less its logit of ``choice``: [T]."""
    import jax.numpy as jnp

    return ref.max(-1) - jnp.take_along_axis(ref, choice[:, None], 1)[:, 0]


def measure(cfg: dict, seed: int, n_seq: int, length: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import cells
    from harness import reference as R
    from harness import weights as W

    fam = cells.family(cfg)
    hybrid_lm = fam.model.program()
    d = fam.leaves.dims(cfg)
    rng = np.random.default_rng(seed & 0xFFFFFFFF)
    toks = rng.integers(0, d["V"], (n_seq, length), dtype=np.int32)
    top = W.top(cfg, seed)
    kinds = [fam.leaves.kind(cfg, i) for i in range(fam.leaves.n_layers(cfg))]

    def chosen_of(kind, w, x):
        """The reference's choice [T, K] (ascending) at layer input x."""
        eps = cfg["rms_norm_eps"]
        f32 = jnp.float32
        u = R._rms(x, w["ln_mixer"].astype(f32), eps)
        mix = kind.partition("_")[0]
        x = x + {"kda": fam.reference._kda,
                 "mla": fam.reference._mla}[mix](cfg, None, w, u)
        h = R._rms(x, w["ln_ffn"].astype(f32), eps)
        s = jax.nn.sigmoid(R._mm("te,ex->tx", h, w["router"].astype(f32),
                                 None))
        gates = fam.reference.route(cfg, s, w["router_bias"].astype(f32))
        return jnp.sort(jnp.argsort(gates == 0, -1, stable=True)
                        [:, :d["Kx"]], -1)

    # the reference and its control, layer by layer
    frozen = W.freeze(cfg)
    xs = [fam.reference.embed(cfg, top, jnp.asarray(t)) for t in toks]
    xcs = list(xs)
    ref_chosen = []                     # [moe layers] of [n_seq * T, K]
    for li, kind in enumerate(kinds):
        w = W.layer(cfg, seed, li)
        if not kind.endswith("_dense"):
            pick = jax.jit(chosen_of, static_argnums=0)
            ref_chosen.append(jnp.concatenate([pick(kind, w, x)
                                               for x in xs]))
        xs = [R._layer_fn(frozen, kind, None)(w, x) for x in xs]
        xcs = [R._layer_fn(frozen, kind, "fp8")(w, x) for x in xcs]
        del w
    rows = jnp.arange(length)
    ref_logits = jax.jit(lambda x: fam.reference.logits(cfg, None, top, x,
                                                        rows))
    ctl_logits = jax.jit(lambda x: fam.reference.logits(cfg, "fp8", top, x,
                                                        rows))
    refs = [ref_logits(x) for x in xs]
    control = [np.asarray(_gaps(r, jnp.argmax(ctl_logits(xc), -1)))
               for r, xc in zip(refs, xcs)]
    del xs, xcs

    # the program, choosing itself and forced
    model = fam.model.build(cfg)
    params = fam.model.place(cfg, top, lambda i: W.layer(cfg, seed, i))
    route = hybrid_lm.sigmoid_route
    seen, forced = [], []

    def patched(logits, bias, top_k, groups, groups_kept, scale):
        gates, chosen = route(logits, bias, top_k, groups, groups_kept,
                              scale)
        seen.append(chosen)
        if forced:
            chosen = forced.pop(0)
            s = jax.nn.sigmoid(logits)
            sc = jnp.take_along_axis(s, chosen, axis=1)
            gates = scale * sc / jnp.sum(sc, -1, keepdims=True)
        return gates, chosen

    def forward(p, tok, force):
        seen.clear()
        forced[:] = list(force)
        logits = model.apply({"params": p}, tok[None])[0]
        return logits, jnp.stack([jnp.sort(c, -1) for c in seen])

    hybrid_lm.sigmoid_route = patched
    try:
        own_fn = jax.jit(lambda p, tok: forward(p, tok, ()))
        forced_fn = jax.jit(forward)
        own, forced_gap, flips = [], [], []
        T = length
        for i, t in enumerate(toks):
            force = [c[i * T:(i + 1) * T] for c in ref_chosen]
            logits, mine = own_fn(params, jnp.asarray(t))
            own.append(np.asarray(_gaps(refs[i], jnp.argmax(logits, -1))))
            flips.append(np.asarray(jnp.any(
                mine != jnp.stack(force), -1)))             # [layers, T]
            logits, _ = forced_fn(params, jnp.asarray(t), force)
            forced_gap.append(np.asarray(
                _gaps(refs[i], jnp.argmax(logits, -1))))
    finally:
        hybrid_lm.sigmoid_route = route

    def summary(gaps):
        g = np.concatenate(gaps)
        return {"max": float(g.max()), "p99": float(np.quantile(g, 0.99)),
                "argmax_share": float((g == 0).mean())}

    flips = np.concatenate(flips, 1)                        # [layers, N*T]
    return {"sequences": n_seq, "length": length,
            "scales": cfg.get("_leaf_scales") or {},
            "flip_share": float(flips.mean()),
            "flip_share_per_layer": [float(f) for f in flips.mean(1)],
            "tokens_with_a_flip_share": float(flips.any(0).mean()),
            "gap_own": summary(own), "gap_forced": summary(forced_gap),
            "gap_control": summary(control)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--length", type=int, default=2048)
    ap.add_argument("--scale", action="append", default=[],
                    help="leaf=factor, any number of times")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, BENCH_DIR]
    from harness import cells
    from harness.runner import overlay, process_env

    process_env(args.tiny)
    import jax

    if not args.tiny and jax.default_backend() != "tpu":
        raise SystemExit("flips.py: the published widths need the TPU "
                         "(--tiny is the CPU run)")
    cfg = cells.Cell(CELL).config
    if args.tiny:
        cfg = overlay(cfg, cfg["tiny"])
    scales = {k: float(v) for k, v in (s.split("=") for s in args.scale)}
    out = measure(_scaled(cfg, scales), args.seed, args.sequences,
                  args.length)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
