"""A cell small enough for the CPU: tiny widths, one prompt bucket."""
import json

from bench import cells


def tiny_config():
    cfg = json.loads((cells.BENCH / "configs" / "mistral-7b.json").read_text())
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
               num_key_value_heads=2, head_dim=64, num_hidden_layers=2,
               vocab_size=512,
               engine={"slots": 4, "prefill_batch": 2, "decode_horizon": 1})
    return cfg


def tiny_cell():
    """The m7b-chat cell at CPU size, served by the reference backend."""
    bench = cells.load_benchmark()
    cell = cells.find(bench, "m7b-chat")
    traffic = dict(cell["traffic"], rate_per_s=6.0, requests=60,
                   preroll_s=0.5, context_limit=64,
                   prompt_tokens={"median": 12, "sigma": 0.3, "min": 9,
                                  "max": 16},
                   output_tokens={"median": 10, "sigma": 0.5, "min": 4,
                                  "max": 24})
    return dict(cell, config=tiny_config(), traffic=traffic)
