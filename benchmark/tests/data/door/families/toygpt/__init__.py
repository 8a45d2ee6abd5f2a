"""A toy second family, kept with the door test and copied into a temporary
benchmark by it: a GPT-2-shaped block (LayerNorm with bias, biased
projections, GELU) whose FFN width is a LIST by layer (``ffn_live``)."""
