"""The granite_hybrid family: Granite 4.0-H (``granitemoehybrid``) — Mamba-2
state-space layers and NoPE attention layers in a pattern, each followed by
routed experts of which this chip holds a share, beside a shared expert."""
