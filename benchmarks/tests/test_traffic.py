"""The stratified schedule and the token-by-timestamp arithmetic."""

import collections
import json

import numpy as np

from benchmarks.lib import loadgen, traffic
from benchmarks.tests.util import HOME

MIX = json.loads((HOME / "traffic" / "batchgen-closed-16.json").read_text())


def test_same_multiset_under_two_seeds_other_order():
    a, b = traffic.schedule(MIX, 1), traffic.schedule(MIX, 2 ** 31 + 7)
    assert len(a) == MIX["clients"] * MIX["blocks"] == 256
    assert collections.Counter(a) == collections.Counter(b)
    assert a != b
    lo, hi = MIX["item_tokens"]
    assert {x for x, _ in a} == set(traffic.quantiles(lo, hi, 16))
    assert min(o for _, o in a) == 64 and max(o for _, o in a) == 384


def test_every_block_holds_every_stratum_once():
    n = MIX["clients"]
    for seed in (3, 4):
        s = traffic.schedule(MIX, seed)
        for i in range(0, len(s), n):
            block = s[i:i + n]
            assert len({x for x, _ in block}) == n
            assert len({o for _, o in block}) == n


def test_requests_share_the_template_and_differ_after_it():
    src = traffic.requests(MIX, 9, 50304)
    (p1, _), (p2, _) = next(src), next(src)
    t = MIX["template_tokens"]
    assert (p1[:t] == p2[:t]).all() and p1.dtype == np.int32
    assert not np.array_equal(p1[t:t + 64], p2[t:t + 64])
    again = next(traffic.requests(MIX, 9, 50304))[0]
    assert np.array_equal(p1, again)          # same seed, same inputs


def test_packed_batches_rows_all_differ_and_repeat_by_seed():
    mix = {"seq_len": 32}
    b = next(traffic.packed_batches(mix, 2 ** 31 + 3, 4, 1000))
    assert b.shape == (4, 32) and len({r.tobytes() for r in b}) == 4
    assert np.array_equal(b, next(traffic.packed_batches(
        mix, 2 ** 31 + 3, 4, 1000)))


def rec(t_send, stamps, t_free=0.0):
    r = loadgen.Record(0, np.zeros(1, np.int32), len(stamps), t_free)
    r.t_send, r.stamps = t_send, list(stamps)
    return r


def test_tokens_counted_by_stamp_across_both_edges():
    records = [
        rec(8.0, [9.0, 9.5, 10.0, 10.5, 11.0]),   # straddles the opening
        rec(12.0, [12.5, 13.0]),                  # inside
        rec(19.0, [19.5, 20.0, 20.5, 21.0]),      # straddles the close
        rec(25.0, [25.5]),                        # after
    ]
    assert loadgen.tokens_in_window(records, 10.0, 20.0) == 3 + 2 + 2
    gaps = loadgen.inter_token_gaps(records, 10.0, 20.0)
    assert len(gaps) == 3 + 1 + 1 and all(abs(g - 0.5) < 1e-9 for g in gaps)
    # first-token time only of requests SENT inside the window
    assert loadgen.first_token_times(records, 10.0, 20.0) == [0.5, 0.5]
    assert loadgen.lateness([rec(12.0, [], t_free=11.75)], 10, 20) == [0.25]
