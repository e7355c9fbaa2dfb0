"""Attribution of device time to the program's named scopes
(bench/lib/scopes.py), the compile counter as the harness reads it
(bench/lib/compiles.py), and the readers of ``hop_kernel_ms``,
``hop_copy_ms``, ``keystream_ms.round`` and ``compile_s.setup``: against
hand-made events and HLO, and against a small trace recorded on the CPU
(data/record_cpu_scope_trace.py)."""
import importlib.util
import os
import shutil
import time

import jax
import numpy as np
import pytest

from bench.lib import compiles, scopes
from bench.lib import trace as tr
from bench.lib.trace import Event, TraceView
from repro.obs.trace import CHAIN_COMBINE, KEYSTREAM, TILE_PAD, TILE_SLICE

HERE = os.path.dirname(__file__)
DATA = os.path.join(HERE, "data", "cpu_scope_window.xplane.pb")
STEPS = 5  # the recording ran its program five times inside the window
FUSED, DOT = "multiply_subtract_fusion", "dot_general.1"


def metric(name):
    spec = importlib.util.spec_from_file_location(
        f"scope_metric_{name}", os.path.join(HERE, "..", "metrics",
                                             name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- the protobuf reader --------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def msg(*fields):
    """A serialized protobuf message of (number, value) fields: an int is a
    varint, bytes / str length-delimited."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def test_wire_fields_of_every_kind():
    buf = (msg((1, 300), (2, "name"), (7, msg((2, "a/b"))))
           + _varint(3 << 3 | 1) + (2**40 + 5).to_bytes(8, "little")
           + _varint(4 << 3 | 5) + (7).to_bytes(4, "little"))
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in scopes.fields(buf)]
    assert got == [(1, 300), (2, b"name"), (7, msg((2, "a/b"))),
                   (3, 2**40 + 5), (4, 7)]
    assert scopes._ints([5, memoryview(_varint(300) + _varint(2))]) == \
        [5, 300, 2]


def _instr(name, op_name="", called=(), opcode="add"):
    fields = [(1, name), (2, opcode)]
    if op_name:
        fields.append((7, msg((1, "op"), (2, op_name))))
    fields += [(38, c) for c in called]
    return msg(*fields)


def _hlo_module():
    """A module whose entry runs a loop whose body runs a fusion: only the
    fusion's computation carries the scope."""
    fused = msg((1, "fused_computation"), (5, 3),
                (2, _instr("sin.0", "jit(f)/keystream/sin")),
                (2, _instr("sub.0", "jit(f)/sub")))
    # a constant XLA shared from another scope does no work here
    body = msg((1, "body"), (5, 2),
               (2, _instr("zero", "jit(f)/tile_pad/convert_element_type",
                          opcode="constant")),
               (2, _instr("fusion.1", "jit(f)/sub", called=[3])))
    entry = msg((1, "main"), (5, 1),
                (2, _instr("while.1", "jit(f)/while", called=[2])),
                (2, _instr("copy.1")))
    return msg((1, msg((1, "jit_f"), (3, fused), (3, body), (3, entry))))


def _xspace(*protos):
    """An XSpace with one metadata plane holding ``protos``."""
    stat_meta = msg((1, 9), (2, msg((1, 9), (2, scopes.HLO_STAT))))
    events = [msg((1, i + 1), (2, msg((1, i + 1), (2, f"m{i}"),
                                       (5, msg((1, 9), (6, p))))))
              for i, p in enumerate(protos)]
    plane = msg((2, "/host:metadata"), (5, stat_meta),
                *[(4, e) for e in events])
    other = msg((2, "/host:CPU"))
    return msg((1, other), (1, plane))


def test_module_scopes_follow_called_computations():
    name, instrs = scopes.module_scopes(_hlo_module())
    assert name == "jit_f"
    assert instrs["fusion.1"] == {"jit(f)/sub", "jit(f)/keystream/sin"}
    assert instrs["while.1"] == {"jit(f)/while", "jit(f)/sub",
                                 "jit(f)/keystream/sin"}
    assert instrs["copy.1"] == frozenset()
    assert instrs["zero"] == frozenset()
    assert scopes.under(instrs["while.1"], {KEYSTREAM})
    assert not scopes.under(instrs["while.1"], {TILE_PAD})
    assert not scopes.under(instrs["copy.1"], {KEYSTREAM})


def test_scope_map_reads_hlo_from_a_metadata_plane():
    m = scopes.scope_map(_xspace(_hlo_module()))
    assert set(m) == {"jit_f"}
    assert scopes.under(m["jit_f"]["fusion.1"], {KEYSTREAM})
    assert scopes.scope_map(msg((1, msg((2, "/host:CPU"))))) == {}


def test_a_scope_is_a_whole_path_component():
    names = {"jit(safe_hop)/jit(chain_combine)/tile_pad/pad"}
    assert scopes.under(names, {TILE_PAD})
    assert not scopes.under(names, {CHAIN_COMBINE})  # only jit(chain_combine)
    assert not scopes.under({"jit(f)/tile_padding/pad"}, {TILE_PAD})
    assert not scopes.under({"jit(f)/keystream"}, {KEYSTREAM})  # an op


def test_module_event_names_drop_their_program_id():
    assert scopes.module_name("jit_safe_hop(6906051471406150894)") == \
        "jit_safe_hop"
    assert scopes.module_name("jit_safe_hop(-12)") == "jit_safe_hop"
    assert scopes.module_name("jit_safe_hop") == "jit_safe_hop"


# --- hand-made windows ----------------------------------------------------

#: one round's programs as a v5e runs them: (module, [(op, ms)])
ROUND = [
    ("jit_safe_initiate", [("pad_bitcast_fusion", 6.0), ("mask_add.1", 20.0),
                           ("fusion.12", 9.7)]),
    *[("jit_safe_hop", [("pad_add_fusion", 0.001),
                        ("pad_bitcast_fusion.1", 6.0),
                        ("pad_bitcast_fusion", 6.0),
                        ("chain_combine.1", 28.8),
                        ("slice_bitcast_fusion", 6.0)])] * 35,
    ("jit_safe_unmask", [("fusion.8", 19.0)]),
    ("jit_probe", [("dynamic_slice.1", 0.01)]),
]

#: the instructions' op_name paths those programs would carry
HLO = {
    "jit_safe_initiate": {
        "pad_bitcast_fusion": {"jit(safe_initiate)/jit(mask_add)/tile_pad/pad"},
        "mask_add.1": {"jit(safe_initiate)/jit(mask_add)/mask_add/mask_add"},
        "fusion.12": {"jit(safe_initiate)/add",
                      "jit(safe_initiate)/jit(mask_add)/tile_slice/slice",
                      "jit(safe_initiate)/keystream/xor"},
    },
    "jit_safe_hop": {
        "pad_add_fusion": {"jit(safe_hop)/jit(chain_combine)/concatenate"},
        "pad_bitcast_fusion": {"jit(safe_hop)/jit(chain_combine)/tile_pad/pad"},
        "pad_bitcast_fusion.1": {
            "jit(safe_hop)/jit(chain_combine)/tile_pad/reshape"},
        "chain_combine.1": {
            "jit(safe_hop)/jit(chain_combine)/chain_combine/chain_combine"},
        "slice_bitcast_fusion": {
            "jit(safe_hop)/jit(chain_combine)/tile_slice/slice"},
    },
    "jit_safe_unmask": {"fusion.8": {"jit(safe_unmask)/sub",
                                     "jit(safe_unmask)/keystream/add"}},
    "jit_probe": {"dynamic_slice.1": {"jit(probe)/dynamic_slice"}},
}


def _window(rounds=2, chips=1, gap_ms=0.5):
    """``rounds`` rounds back to back on each of ``chips`` chips; each
    program's ops fill its module event, a gap between programs."""
    ops, mods = [], []
    for _ in range(chips):
        dev_ops, dev_mods, t = [], [], 0.0
        for _ in range(rounds):
            for j, (mod, prog) in enumerate(ROUND):
                start = t
                for name, ms in prog:
                    dev_ops.append(Event(f"%{name} = u32[8] fusion()", t,
                                         t + ms * 1e6))
                    t += ms * 1e6
                dev_mods.append(Event(f"{mod}({j % 3 + 7})", start, t))
                t += gap_ms * 1e6
        ops.append(dev_ops)
        mods.append(dev_mods)
    return TraceView(lo=0.0, hi=t, ops=ops, modules=mods, host=[],
                     units=rounds, counts={"update_words": 493_753_344},
                     peaks={"hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("chips", [1, 2])
def test_scope_s_splits_the_hop(chips):
    v = _window(chips=chips)
    kernel = scopes.scope_s(v, HLO, "jit_safe_hop", CHAIN_COMBINE)
    copies = scopes.scope_s(v, HLO, "jit_safe_hop", (TILE_PAD, TILE_SLICE))
    assert kernel == (pytest.approx(2 * 35 * 28.8e-3), 70)
    assert copies == (pytest.approx(2 * 35 * 18.0e-3), 70)
    hop_s, runs = v.module_s("jit_safe_hop")
    assert runs == 70
    # the scalar packing is the only unscoped work of a hop
    assert hop_s - kernel[0] - copies[0] == pytest.approx(70 * 0.001e-3)


def test_an_operation_under_two_scopes_counts_once():
    v = _window()
    both = scopes.scope_s(v, HLO, "jit_safe_initiate", (TILE_SLICE, KEYSTREAM))
    assert both == (pytest.approx(2 * 9.7e-3), 2)


def test_ops_outside_any_module_or_module_prefix_are_left_out():
    v = _window(rounds=1)
    stray = Event("%fusion.8 = u32[8] fusion()", v.hi + 1, v.hi + 2)
    v.ops[0].append(stray)
    assert scopes.scope_s(v, HLO, "", KEYSTREAM) == \
        (pytest.approx((9.7 + 19.0) * 1e-3), len(ROUND))
    assert scopes.scope_s(v, HLO, "jit_safe_hop", KEYSTREAM) == (0.0, 35)
    assert scopes.scope_s(v, {}, "", KEYSTREAM) == (0.0, len(ROUND))


@pytest.fixture
def hand_hlo(monkeypatch):
    monkeypatch.setattr(scopes, "hlo_of", lambda view: HLO)


def test_kernel_and_copies_make_up_hop_ms(hand_hlo):
    v = _window()
    hop = metric("hop_ms").read(v)
    kernel = metric("hop_kernel_ms").read(v)
    copies = metric("hop_copy_ms").read(v)
    assert kernel == pytest.approx(28.8)
    assert copies == pytest.approx(18.0)
    assert kernel + copies == pytest.approx(hop, rel=1e-4)


def test_keystream_is_read_per_round_in_every_program(hand_hlo):
    assert metric("keystream_ms.round").read(_window(rounds=3)) == \
        pytest.approx(9.7 + 19.0)


@pytest.mark.parametrize("name", ["hop_kernel_ms", "hop_copy_ms",
                                  "keystream_ms.round"])
def test_a_program_without_scopes_reads_nothing(monkeypatch, name):
    """The parent program: no scope names, or scopes the HLO lacks."""
    monkeypatch.setattr(scopes, "hlo_of", lambda view: HLO)
    monkeypatch.setattr(scopes, "program_scopes", lambda *a: None)
    assert metric(name).read(_window()) is None
    monkeypatch.undo()
    monkeypatch.setattr(scopes, "hlo_of", lambda view: {})
    assert metric(name).read(_window()) is None


@pytest.mark.parametrize("name", ["hop_kernel_ms", "hop_copy_ms"])
def test_a_window_without_a_hop_reads_nothing(hand_hlo, name):
    v = _window()
    keep = [i for i, m in enumerate(v.modules[0])
            if not m.name.startswith("jit_safe_hop")]
    v.modules[0] = [v.modules[0][i] for i in keep]
    assert metric(name).read(v) is None


def test_program_scopes_are_the_programs_names():
    assert scopes.program_scopes("KEYSTREAM", "TILE_PAD") == \
        (KEYSTREAM, TILE_PAD)
    assert scopes.program_scopes("NO_SUCH_SCOPE") is None


# --- the recorded CPU trace -----------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """The recording as a window: the CPU runs its operations on host
    threads and has no module line, so each step span stands for one run
    of the program's module."""
    planes = tr.load(DATA)
    host = tr.host_events(planes)
    lo, hi = tr.window(host)
    ops = tr.clip([e for e in host if e.name in (FUSED, DOT)], lo, hi)
    steps = [Event("jit_program(1)", e.start_ns, e.end_ns) for e in host
                if e.name == "step"]
    view = TraceView(lo, hi, [ops], [steps], host, STEPS, {}, {})
    with open(DATA, "rb") as f:
        return view, scopes.scope_map(f.read())


def test_recorded_hlo_names_the_fused_scope(recorded):
    _, hlo = recorded
    prog = hlo["jit_program"]
    assert scopes.under(prog[FUSED], {KEYSTREAM})
    assert "jit(program)/sub" in prog[FUSED]  # the fusion's unscoped root
    assert not scopes.under(prog[DOT], {KEYSTREAM})


def test_recorded_window_attributes_the_fusion(recorded):
    view, hlo = recorded
    ops = view.ops[0]
    assert sum(e.name == FUSED for e in ops) == STEPS
    fused_s = sum(e.dur_ns for e in ops if e.name == FUSED) * 1e-9
    dot_s = sum(e.dur_ns for e in ops if e.name == DOT) * 1e-9
    got, runs = scopes.scope_s(view, hlo, "jit_program", KEYSTREAM)
    assert runs == STEPS and got == pytest.approx(fused_s) and got > 0
    every, _ = scopes.scope_s(view, hlo, "", (KEYSTREAM, "dot_general"))
    assert every == pytest.approx(fused_s)  # an op's name is no scope
    assert scopes.scope_s(view, hlo, "jit_other", KEYSTREAM) == (0.0, 0)
    assert dot_s > 0


def test_the_trace_file_is_found_by_its_window(recorded, tmp_path,
                                               monkeypatch):
    view, _ = recorded
    monkeypatch.setattr(scopes, "_found", {})
    where = tmp_path / "cell" / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    shutil.copy(DATA, where / "host.xplane.pb")
    shutil.copy(os.path.join(HERE, "data", "cpu_window.xplane.pb"),
                tmp_path / "cell" / "plugins" / "profile" / "other.xplane.pb")
    assert scopes.trace_file(view, str(tmp_path)) == \
        str(where / "host.xplane.pb")
    other = TraceView(view.lo + 1, view.hi, [], [], [], 1, {}, {})
    assert scopes.trace_file(other, str(tmp_path)) is None
    start = scopes.profile_start_ns(DATA)
    assert start > 1.7e18  # wall-clock ns, after 2023
    monkeypatch.setattr(scopes, "trace_file", lambda v: DATA)
    began = scopes.window_start_s(view)
    assert began == pytest.approx((start + view.lo) * 1e-9)
    # the window's times are offsets from the trace's start, not wall
    # clock: the window began after the trace did and before the
    # recording was written
    assert 0 <= view.lo < 86_400e9
    assert start * 1e-9 <= began <= os.path.getmtime(DATA)


def test_the_trace_dir_is_the_harness_s():
    import bench.run as run
    assert scopes.TRACE_DIR == run.TRACE_DIR


# --- compile_s.setup ------------------------------------------------------

def test_setup_seconds_is_the_count_before_the_window():
    assert compiles.REGISTRY is not None
    jax.jit(lambda x: x * 11 - 1)(np.ones(13, np.float32))
    after = time.time()
    total = compiles.REGISTRY.counter("jax_compile_seconds_total").value
    assert total > 0
    assert compiles.setup_seconds(after + 1) == pytest.approx(total)
    # a compile that ended after a window began: the total is not set-up
    assert compiles.setup_seconds(after - 3600) is None
    assert compiles.setup_seconds(None) is None


def test_a_compile_marks_its_end():
    f, x = jax.jit(lambda x: x * 17 - 1), np.ones(13, np.float32)
    f(x)
    ended = compiles.last_compile_s
    assert time.time() - 60 < ended <= time.time()
    f(x)  # compiled already
    assert compiles.last_compile_s == ended
    assert compiles.setup_seconds(ended) is None
    assert compiles.setup_seconds(ended + 1e-3) > 0


def test_compile_reader_reads_the_window_start(monkeypatch):
    reader = metric("compile_s.setup")
    jax.jit(lambda x: x * 13 - 1)(np.ones(13, np.float32))
    monkeypatch.setattr(scopes, "window_start_s", lambda view: time.time())
    assert reader.read(None) > 0
    monkeypatch.setattr(scopes, "window_start_s", lambda view: None)
    assert reader.read(None) is None
    monkeypatch.setattr(compiles, "REGISTRY", None)
    monkeypatch.setattr(scopes, "window_start_s", lambda view: time.time())
    assert reader.read(None) is None
