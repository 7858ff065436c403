"""Pallas TPU kernels for the technique's hot data-movement paths.

dispatch     — routing-plan gather (the redistribution data movement)
histogram    — destination load counts (skew-model input, every step)
topk_gating  — fused softmax + top-k routing
ssd_scan     — Mamba-2 inter-chunk state recurrence

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper: kernel or reference), ref.py (pure-jnp oracle).
"""
