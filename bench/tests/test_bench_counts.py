"""Work counts of the per-layer metrics against hand arithmetic, and each
reader against a hand-made traced window."""
import importlib.util
import os

import pytest

from bench.lib.trace import Event, TraceView

METRICS = os.path.join(os.path.dirname(__file__), "..", "metrics")
V = 493_753_344  # internvl2-1b's parameters: the round cell's update


def metric(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hop_needs_twelve_bytes_a_word():
    hop = metric("hop_roofline")
    # read cipher 4 B + read update 4 B + write cipher 4 B
    assert hop.hop_bytes(V) == 12 * V
    # one Threefry-2x32 block per two words per pad, two pads
    assert hop.threefry_blocks(V) == V
    assert hop.threefry_blocks(5) == 6


def _round_view(hop_ms, hops, idle_ms=0.0):
    """One chip's window: ``hops`` hop programs of ``hop_ms`` each, back to
    back after an initiate of 20 ms, then ``idle_ms`` with nothing run."""
    ops, mods, t = [], [], 0.0
    for name, ms in [("jit_safe_initiate", 20.0)] + [("jit_safe_hop", hop_ms)
                                                     ] * hops:
        ops.append(Event("fusion", t, t + ms * 1e6))
        mods.append(Event(f"{name}(7)", t, t + ms * 1e6))
        t += ms * 1e6
    return TraceView(lo=0.0, hi=t + idle_ms * 1e6, ops=[ops], modules=[mods],
                     host=[], units=1, counts={"update_words": V},
                     peaks={"hbm_bytes_per_s": 819e9})


def test_hop_ms_is_the_hop_programs_per_run():
    assert metric("hop_ms").read(_round_view(46.8, 35)) == pytest.approx(46.8)


def test_hop_roofline_is_the_needed_hbm_time_over_the_hop():
    want = 100 * (12 * V / 819e9) / 46.8e-3
    assert metric("hop_roofline").read(_round_view(46.8, 35)) == \
        pytest.approx(want)


def test_device_idle_is_the_unbusy_share():
    v = _round_view(40.0, 9, idle_ms=20.0)  # 380 ms busy of 400
    assert metric("device_idle.round").read(v) == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["hop_ms", "hop_roofline"])
def test_a_window_without_a_hop_reads_nothing(name):
    assert metric(name).read(_round_view(46.8, 0)) is None
