"""The train cell's per-layer readers (``*.safe_step``): work counts against
hand arithmetic, each reader against a hand-made traced window, and the
nesting helper the scope readers share (bench/lib/nesting.py)."""
import importlib.util
import os
import time

import jax
import numpy as np
import pytest

from bench.lib import compiles, scopes
from bench.lib.nesting import outermost, outermost_view
from bench.lib.trace import Event, TraceView

METRICS = os.path.join(os.path.dirname(__file__), "..", "metrics")

#: the train cell's counts (bench/drivers/train_step.py) at 4 x 4 x 2,048
TRAIN = {"tokens_per_step": 4 * 4 * 2048, "seq_len": 2048, "layers": 4,
         "hidden_size": 2048, "intermediate_size": 8192,
         "num_attention_heads": 16, "num_key_value_heads": 8,
         "vocab_size": 92544, "tie_word_embeddings": False,
         "update_words": 630_736_896}


def metric(name):
    spec = importlib.util.spec_from_file_location(
        f"train_metric_{name}", os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_device_idle_is_the_unbusy_share():
    # nine 40 ms steps back to back, then 20 ms with nothing run
    ops = [Event("fusion", i * 40e6, (i + 1) * 40e6) for i in range(9)]
    v = TraceView(lo=0.0, hi=380e6, ops=[ops], modules=[[]], host=[],
                  units=1, counts=TRAIN, peaks={})
    assert metric("device_idle.safe_step").read(v) == pytest.approx(
        100 * 20 / 380)


def test_step_flops_is_six_n_t_plus_causal_attention():
    mfu = metric("mfu.safe_step")
    T = 32768
    # a layer's matrices: q 2048x2048, k and v 2048x1024, o 2048x2048, and
    # the SwiGLU's three 2048x8192; the untied head 92544x2048
    n = 4 * (2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192) \
        + 92544 * 2048
    assert n == 630_736_896 - 92544 * 2048 - 4 * 2 * 2048 - 2048
    attention = 4 * (2 * 2048 * 16 * 128) * 3 * T  # causal, fwd + bwd
    assert mfu.step_flops(TRAIN) == 6 * n * T + attention


def test_mfu_is_model_flops_over_the_step_programs_peak():
    mfu = metric("mfu.safe_step")
    chips, step_ms = 4, 800.0
    ops = [[Event("fusion", 0.0, step_ms * 1e6)] for _ in range(chips)]
    mods = [[Event("jit_train_step(3)", 0.0, step_ms * 1e6)]
            for _ in range(chips)]
    v = TraceView(lo=0.0, hi=step_ms * 1e6, ops=ops, modules=mods, host=[],
                  units=1, counts=TRAIN, peaks={"bf16_flops_per_s": 197e12})
    want = 100 * mfu.step_flops(TRAIN) / (step_ms * 1e-3 * chips * 197e12)
    assert mfu.read(v) == pytest.approx(want)
    assert 0 < want <= 100


def test_outermost_counts_a_loop_and_its_body_once():
    loop = Event("while.5", 0.0, 100.0)
    body = [Event("fusion.1", 10.0, 40.0), Event("convolution.2", 40.0, 90.0)]
    after = [Event("fusion.3", 95.0, 130.0), Event("copy.4", 130.0, 140.0)]
    assert outermost(body + [loop] + after) == [loop] + after
    assert outermost(after) == after  # nothing nested: nothing dropped
    v = TraceView(lo=0.0, hi=140.0, ops=[[loop] + body + after], modules=[[]],
                  host=[], units=1, counts={}, peaks={})
    cut = outermost_view(v)
    assert cut.ops == [[loop] + after] and (cut.lo, cut.hi) == (v.lo, v.hi)
    assert cut.busy_s() == v.busy_s()


def test_train_compile_reader_reads_the_window_start(monkeypatch):
    reader = metric("compile_s.safe_step")
    jax.jit(lambda x: x * 19 - 1)(np.ones(13, np.float32))
    monkeypatch.setattr(scopes, "window_start_s", lambda view: time.time())
    assert reader.read(None) > 0
    monkeypatch.setattr(scopes, "window_start_s", lambda view: None)
    assert reader.read(None) is None
    monkeypatch.setattr(compiles, "REGISTRY", None)
    monkeypatch.setattr(scopes, "window_start_s", lambda view: time.time())
    assert reader.read(None) is None
