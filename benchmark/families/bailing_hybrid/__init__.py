"""The bailing_hybrid family: inclusionAI Ling 3.0 (``bailing_hybrid``) —
gated delta-rule (KDA) layers and latent-attention (MLA) layers in periods
of ``layer_group_size``, the first ``first_k_dense_replace`` with a dense
SwiGLU and the rest with sigmoid-routed experts of which this chip holds a
share, beside a shared expert."""
