"""The traffic generator: seeds, decks and the stated distributions."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import traffic  # noqa: E402

MIX = json.loads((BENCH / "traffic" / "saturated-exits.json").read_text())


def test_same_seed_same_requests():
    a = traffic.make_backlog(MIX, 2**31 + 12345, 90, 151936)
    b = traffic.make_backlog(MIX, 2**31 + 12345, 90, 151936)
    c = traffic.make_backlog(MIX, 2**31 + 12346, 90, 151936)
    for x, y in ((a.prompt_len, b.prompt_len), (a.n_tokens, b.n_tokens), (a.prompts, b.prompts)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.prompts, c.prompts)
    assert not np.array_equal(a.n_tokens, c.n_tokens)


def test_longer_backlog_only_appends():
    short = traffic.make_backlog(MIX, 7, 45, 1000)
    long = traffic.make_backlog(MIX, 7, 130, 1000)
    np.testing.assert_array_equal(short.prompt_len, long.prompt_len[:45])
    np.testing.assert_array_equal(short.n_tokens, long.n_tokens[:45])
    np.testing.assert_array_equal(short.prompts, long.prompts[:45])


def test_prompt_lengths_as_stated():
    lens = traffic.prompt_lengths(MIX)
    n = len(lens)
    share = {L: lens.count(L) / n for L in MIX["prompt_lengths"]}
    assert share == {128: 0.4, 512: 0.3, 1024: 0.2, 2048: 0.1}
    assert np.mean(lens) == pytest.approx(614.4)


def test_output_lengths_heavy_tailed_16_to_512_mean_about_128():
    out = traffic.output_lengths(MIX)
    assert min(out) == 16 and max(out) == 512
    assert 120 <= np.mean(out) <= 136
    assert np.median(out) < np.mean(out)  # right-skewed


@pytest.mark.parametrize("seed", [0, 1, 2**33 + 5])
def test_every_deck_holds_the_same_sizes(seed):
    d = MIX["deck"]
    pl, nt = traffic.sizes(MIX, seed, 5 * d)
    for k in range(5):
        assert sorted(pl[k * d:(k + 1) * d]) == sorted(traffic.prompt_lengths(MIX))
        assert sorted(nt[k * d:(k + 1) * d]) == sorted(traffic.output_lengths(MIX))


def test_prompt_tokens_in_vocab():
    b = traffic.make_backlog(MIX, 3, 20, 151936)
    assert b.prompts.min() >= 0 and b.prompts.max() < 151936
    assert b.prompts.shape == (20, 2048)


def test_warmup_waves_fill_every_slot_with_every_length():
    for pl, nt in traffic.warmup_waves(MIX, 8):
        assert len(pl) == 8 and set(pl) == set(MIX["prompt_lengths"])
        assert len(set(nt)) == 1
