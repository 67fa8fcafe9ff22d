"""The CPU tests' tiny cell: the Qwen3 block at toy widths
(``data/tiny.json``) under a short github-like mix (``data/tiny-mix.json``)."""

import json
from pathlib import Path

import check
import harness
from traffic import gen

DATA = Path(__file__).resolve().parent / "data"
END_TO_END = [{"name": "tokens_per_s", "unit": "tokens/s"},
              {"name": "setup_s", "unit": "s"}]


def cell(chips: int = 1) -> harness.Cell:
    return harness.Cell(
        name="tiny", config=json.loads((DATA / "tiny.json").read_text()),
        traffic=gen.load_mix("tiny-mix", DATA), chips=chips,
        limits=check.load_limits("tiny-limits", DATA),
        end_to_end=END_TO_END, per_layer=[])


def run(chips: int = 1, seed: int = 7) -> dict:
    return harness.execute(cell(chips), seed, 0.5, False, require_tpu=False,
                           log=lambda m: None)
