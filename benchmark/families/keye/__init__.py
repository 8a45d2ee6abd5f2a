"""The keye family (Keye-VL-2.0's language model): a Qwen3-MoE block —
RMSNorm, rotary GQA attention with a head width of its own and per-head q/k
RMSNorm, dropless softmax-routed SiLU experts in every layer, untied head —
whose attention reads, for every query, only the ``sa_config.topk``
positions that a learned indexer (DeepSeek-V3.2's lightning indexer: a few
small query heads, one index key a token) scores highest.  Text only: the
vision tower is not in the repository."""
