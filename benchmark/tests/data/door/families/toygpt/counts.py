"""The toy family's counts: summed over the layers, each by its kind."""

import math

from . import leaves


def _mm_params(cfg, kind):
    E, H, KH = cfg["n_embd"], cfg["n_head"], cfg["n_kv_head"]
    D = E // H
    return 2 * E * H * D + 2 * E * KH * D + 2 * E * kind


def _kinds(cfg):
    return [leaves.kind(cfg, i) for i in range(leaves.n_layers(cfg))]


def token_flops(cfg, ctx):
    return sum(2.0 * _mm_params(cfg, k) + 4.0 * ctx * cfg["n_embd"]
               for k in _kinds(cfg))


def span_flops(cfg, first, count):
    ctx_sum = count * first + count * (count + 1) // 2
    return sum(2.0 * _mm_params(cfg, k) * count
               + 4.0 * ctx_sum * cfg["n_embd"] for k in _kinds(cfg))


def head_flops(cfg):
    return 2.0 * cfg["n_embd"] * cfg["n_vocab"]


def kv_bytes(cfg, ctx, kv_itemsize=2):
    D = cfg["n_embd"] // cfg["n_head"]
    return ctx * 2 * leaves.n_layers(cfg) * cfg["n_kv_head"] * D \
        * kv_itemsize


def paged_attn_bytes(cfg, ctx, block_size, kv_itemsize=2):
    return kv_bytes(cfg, -(-ctx // block_size) * block_size, kv_itemsize)


def n_params(cfg):
    count = lambda ls: sum(math.prod(s) for s, _, _ in ls.values())
    return count(leaves.top_leaves(cfg)) + sum(
        count(leaves.layer_leaves(cfg, k)) for k in _kinds(cfg))


def weight_bytes(cfg, itemsize=2):
    return n_params(cfg) * itemsize
