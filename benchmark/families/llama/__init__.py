"""The llama family: RMSNorm, rotary GQA attention (with bias where the
configuration has ``attention_bias``), SwiGLU, tied or untied head — every
layer the same kind.  Qwen2.5 and Mistral are of it."""
