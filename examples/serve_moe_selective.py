"""Serve a MoE model (kimi-k2 family, reduced) with mixed det/nondet
traffic — the family where router flips make DVR matter most.

Run:  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_moe_selective.py

``--smoke`` picks the reduced config: without it the published kimi-k2
(1T parameters) would be built.
"""
from repro.launch.serve import main as serve_main

if __name__ == "__main__":
    serve_main([
        "--smoke", "--arch", "kimi-k2-1t-a32b", "--requests", "8",
        "--det-ratio", "0.25", "--max-new", "24", "--mode", "llm42",
        "--window", "6", "--group", "2",
    ])
