"""A cell at CPU scale for the tests."""

import json
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"


def tiny_spec(mean: float = 1e9):
    """A cell at CPU scale: granite's shape family, chat-like traffic, the
    check as the cells have it (``mean`` the limit of the mean gap)."""
    from chipbench import loadgen
    return {
        "cell": {"name": "tiny.chat", "chips": 1},
        "config": json.loads((DATA / "tiny-moe.json").read_text()),
        "mix": loadgen.load_mix(DATA / "tiny-chat.json"),
        "limits": {"compared": {
            "mean_logit_gap": {"limit": mean},
            "compared_tokens": {"min": 16}},
            "sample_tokens": 8192, "sample_requests": 32},
        "end_to_end": [{"name": n, "unit": "x"} for n in
                       ("ttft_p50_ms", "output_tok_s", "setup_s")],
        "per_layer": [],
    }
