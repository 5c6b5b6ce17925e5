"""Request times, tails and rates rebuilt from recorder events."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.requests import reconstruct


def span(name, t0, t1, **args):
    return SimpleNamespace(kind="span", name=name, ts=t0, dur=t1 - t0, args=args)


def instant(name, ts, **args):
    return SimpleNamespace(kind="instant", name=name, ts=ts, dur=None, args=args)


# window [0, 10]; decode dispatches each emit one token per active slot
EVENTS = [
    instant("enqueue", 0.5, rid=1),
    instant("enqueue", 1.0, rid=2),
    span("admit", 1.0, 1.5, rid=1),
    span("decode_step", 2.0, 3.0, n_active=1),
    span("decode_step", 3.0, 4.0, n_active=1),
    instant("retire", 4.1, rid=1),  # request 1: tokens at 3 and 4
    span("chunk", 4.2, 5.0, rid=2, final=False),
    span("chunk", 5.0, 5.5, rid=2, final=True),
    span("decode_step", 6.0, 7.0, n_active=1),
    span("decode_step", 7.0, 8.0, n_active=1),
    instant("enqueue", 8.0, rid=3),  # never admitted: censored at the cut
    span("decode_step", 8.0, 9.0, n_active=1),
    span("decode_step", 9.0, 10.0, n_active=1),  # request 2: 7, 8, 9, 10
    span("decode_step", 10.0, 11.0, n_active=1),  # ends after the cut
    instant("enqueue", 11.0, rid=4),  # arrives after the cut
]


def test_first_tokens_tokens_and_gaps():
    t = reconstruct(EVENTS, 0.0, 10.0)
    assert sorted(t.arrived) == [1, 2, 3]
    assert t.first == {1: 3.0, 2: 7.0}
    assert t.served == {1: 2, 2: 4}
    assert t.tokens == 6 and t.decode_steps == 6
    assert sorted(t.gaps.tolist()) == [1.0, 1.0, 1.0, 1.0]


def test_ttft_counts_a_request_cut_before_its_first_token():
    t = reconstruct(EVENTS, 0.0, 10.0)
    # 3.0 - 0.5, 7.0 - 1.0, and request 3's wait at the cut, 10 - 8
    assert sorted(t.ttft.tolist()) == [2.0, 2.5, 6.0]
    assert t.ttft_p95 == pytest.approx(np.percentile([2.0, 2.5, 6.0], 95))
    assert t.ttft_p50 == pytest.approx(2.5)


def test_a_stall_cannot_shorten_the_tail():
    # the same window cut before request 2's first token: its wait to the
    # cut enters the sample, so the tail grows with the stall
    cut = reconstruct(EVENTS, 0.0, 6.5)
    assert 2 not in cut.first
    assert sorted(cut.ttft.tolist()) == [2.5, 5.5]
    assert cut.tokens == 2


def test_empty_window():
    t = reconstruct([], 0.0, 1.0)
    assert t.tokens == 0 and np.isnan(t.tpot_p95) and np.isnan(t.ttft_p95)
