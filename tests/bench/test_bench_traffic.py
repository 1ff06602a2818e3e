"""The one traffic generator: every seed offers the same work in another
order."""
import json
from collections import Counter
from pathlib import Path

import pytest

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
CHAT = json.loads((ROOT / "bench/traffic/serve.phi4mini.chat.json")
                  .read_text())
SEEDS = [3, 2 ** 31 + 1, 2 ** 33 + 7]


def test_every_seed_offers_the_same_gaps_and_sizes_in_another_order():
    plans = [traffic.requests(CHAT, 51, s) for s in SEEDS]
    n = int(CHAT["arrivals"]["rate"] * 51)
    assert all(len(p) == n for p in plans)
    gaps = [sorted(round(b[0] - a[0], 9) for a, b in zip([(0,)] + p, p))
            for p in plans]
    assert gaps[1] == pytest.approx(gaps[0])
    assert gaps[2] == pytest.approx(gaps[0])
    for k in (1, 2):
        assert all(Counter(r[k] for r in p) == Counter(r[k] for r in plans[0])
                   for p in plans)
    assert len({tuple(r[1:] for r in p) for p in plans}) == len(SEEDS)
    assert traffic.requests(CHAT, 51, SEEDS[0]) == plans[0]


def test_the_lengths_match_the_source_means_and_limits():
    plan = traffic.requests(CHAT, 51, SEEDS[0])
    out = [o for _, _, o in plan]
    assert sum(out) / len(out) == pytest.approx(214.5, rel=0.03)
    for (_, p, o) in plan:
        assert 4 <= o <= 1024 and p <= 1024 and p % 16 == 0


def test_prompt_lengths_are_every_length_a_window_offers():
    lens = traffic.prompt_lengths(CHAT, 51)
    for s in SEEDS:
        assert {p for _, p, _ in traffic.requests(CHAT, 51, s)} == set(lens)


@pytest.mark.parametrize("bad", [{"arrivals": {"kind": "bursty"}},
                                 {"prompt": {"dist": "uniform"}}])
def test_an_unknown_kind_is_refused(bad):
    with pytest.raises(ValueError, match="unknown"):
        traffic.requests(dict(CHAT, **bad), 51, 1)
