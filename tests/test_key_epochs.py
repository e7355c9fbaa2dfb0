"""Key epochs (Round-0 key rotation): the aggregator's counter reservations
never wrap, a new epoch gives fresh pads at the same counter, a rotation
compiles nothing, and a resumed training run never reuses a pad."""
import json
import os

import jax
import numpy as np
import pytest

from helpers import run_multidevice
from repro.core import make_aggregator, make_round_keys
from repro.core.aggregators import KEY_EPOCHS
from repro.core.chain import _TAG_HOP_PAD, _TAG_INITIATOR_MASK
from repro.crypto.prf import RoundCounter, derive_key, keystream_pair_lanes
from repro.launch.compile_cache import COMPILES, watch_compiles
from repro.obs import MetricsRegistry

#: the published InternLM2-1.8B update cut to 4 layers (fed4 cell's V)
V_PUBLISHED_L4 = 630_736_896


def test_reserve_round_opens_a_new_epoch_at_base_zero():
    agg = make_aggregator("safe", 4)
    steps = RoundCounter.LIMIT // V_PUBLISHED_L4
    got = [agg.reserve_round(V_PUBLISHED_L4) for _ in range(2 * steps + 1)]
    assert got[:steps] == [(0, k * V_PUBLISHED_L4) for k in range(steps)]
    # the 7th would cross 2**32: it opens epoch 1 at 0 instead of wrapping
    assert got[steps:2 * steps] == [(1, k * V_PUBLISHED_L4)
                                    for k in range(steps)]
    assert got[-1] == (2, 0)
    for epoch, base in got:
        assert base + V_PUBLISHED_L4 <= RoundCounter.LIMIT
    assert agg.metrics.counter(KEY_EPOCHS).value == 2


def test_reserve_round_refuses_a_round_larger_than_an_epoch():
    agg = make_aggregator("safe", 4)
    with pytest.raises(OverflowError):
        agg.reserve_round(RoundCounter.LIMIT + 1)
    assert agg.reserve_round(RoundCounter.LIMIT) == (0, 0)
    assert agg.reserve_round(1) == (1, 0)


def test_key_state_after_and_resume():
    agg = make_aggregator("safe", 4)
    agg.reserve_round(10)
    lease = agg.key_state_after(3, 2**31)
    # 10 + 2**31 fits epoch 0; the next 2**31 does not: epoch 1 holds two
    assert lease == {"key_epoch": 1, "counter_next": 2**32}
    assert agg.reserve_round(10) == (0, 10)  # the lease reserved nothing
    other = make_aggregator("safe", 4)
    other.resume(**lease)
    assert other.reserve_round(1) == (2, 0)
    with pytest.raises(ValueError):
        other.resume(2**32, 0)


def _pads(epoch, base, n=64):
    """Rank 0's outgoing hop pad and initiator mask at (epoch, base)."""
    keys = make_round_keys(0xC0FFEE, 0x5EED, base, rank=0, epoch=epoch)
    hop = derive_key(derive_key(keys.provisioning_seed, _TAG_HOP_PAD), 0, 1)
    own = derive_key(keys.learner_seed, _TAG_INITIATOR_MASK)
    return (np.asarray(keystream_pair_lanes(hop, n, keys.counter_base)),
            np.asarray(keystream_pair_lanes(own, n, keys.counter_base)))


def test_two_epochs_at_one_counter_give_different_pads():
    for a, b in zip(_pads(0, 1234), _pads(1, 1234)):
        assert np.mean(a == b) < 0.01
    for a, b in zip(_pads(5, 1234), _pads(5, 1234)):
        np.testing.assert_array_equal(a, b)


def test_an_epoch_change_compiles_nothing():
    reg = watch_compiles(MetricsRegistry())

    @jax.jit
    def pads(epoch, base):
        keys = make_round_keys(0xC0FFEE, 0x5EED, base, rank=0, epoch=epoch)
        return keystream_pair_lanes(
            derive_key(keys.learner_seed, _TAG_INITIATOR_MASK), 256,
            keys.counter_base)

    first = pads(np.uint32(0), np.uint32(0))
    before = reg.counter(COMPILES).value
    again = pads(np.uint32(1), np.uint32(0))
    assert reg.counter(COMPILES).value == before
    assert not np.array_equal(np.asarray(first), np.asarray(again))


def test_resumed_training_never_reuses_a_pad(tmp_path):
    """The launcher's checkpoint carries the aggregator's key state: the
    first counter of a resumed run lies past every counter the first run
    used, including those of the steps it ran after its checkpoint."""
    ckpt, log = tmp_path / "ckpt", tmp_path / "metrics.jsonl"
    run = f"""
import sys
from repro.launch.train import main
sys.argv = ["train", "--arch", "internlm2-1.8b", "--smoke", "--steps", "{{}}",
            "--learners", "4", "--model-shards", "1", "--seq-len", "32",
            "--batch-per-learner", "1", "--ckpt-dir", "{ckpt}",
            "--ckpt-every", "2", "--metrics", "{log}"]
main()
"""
    out = run_multidevice(run.format(3), devices=4)
    assert "done" in out
    first = [json.loads(line) for line in log.read_text().splitlines()]
    out = run_multidevice(run.format(5), devices=4)
    assert "resumed from step 2" in out
    second = [json.loads(line) for line in log.read_text().splitlines()]
    second = second[len(first):]
    used = {(r["key_epoch"], r["counter"]) for r in first}
    assert [r["step"] for r in first] == [0, 1, 2]
    assert [r["step"] for r in second] == [2, 3, 4]
    assert min((r["key_epoch"], r["counter"]) for r in second) > max(used)
    assert os.path.isdir(ckpt)



@pytest.mark.parametrize("n", [3, 4, 5])
@pytest.mark.parametrize("start", ["fresh", "resumed_mid_epoch"])
def test_the_initiator_moves_on_every_step_across_epochs(n, start):
    """The train step's initiator offset differs from the previous step's
    on every step, across key-epoch boundaries too, whether the run began
    at (0, 0) or resumed at a base that is no whole number of steps."""
    from repro.train.train_step import initiator_rotation
    agg = make_aggregator("safe", n)
    if start == "resumed_mid_epoch":
        agg.resume(2**31 + 7, 2**32 - V_PUBLISHED_L4 - 12345)
    slots = [agg.reserve_round(V_PUBLISHED_L4) for _ in range(20)]
    assert len({epoch for epoch, _ in slots}) >= 3
    got = [int(initiator_rotation(np.uint32(e), np.uint32(b),
                                  V_PUBLISHED_L4, n)) for e, b in slots]
    assert all(0 <= r < n for r in got)
    assert all(a != b for a, b in zip(got, got[1:])), got
    if start == "fresh":  # the running index itself, mod n
        assert got == [k % n for k in range(20)]
