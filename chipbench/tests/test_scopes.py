"""``chipbench/scopes.py``: device time by named scope and idle gaps by program span, on hand-made
events, on hand-encoded protobuf, on a trace recorded here on the CPU (host spans only), and on a small
trace recorded on a v5e by ``make_scopes_toy.py`` (``data/scopes_toy.xplane.pb``: a toy train step with
scopes under three host spans, six iterations of which every second one flushes under a span and the
others sleep under none; PR 28, call 13)."""

from pathlib import Path

import pytest

from chipbench import scopes

TOY = Path(__file__).parent / "data" / "scopes_toy.xplane.pb"


# ---- the protobuf wire format ---------------------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number, payload):
    """A length-delimited field (strings, bytes, messages)."""
    payload = payload.encode() if isinstance(payload, str) else payload
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def stat(metadata_id, number, value):
    """One XStat: its metadata id and one value field (3: uint64, 5: str, 7: ref)."""
    return varint(1 << 3 | 0) + varint(metadata_id) + (field(number, value) if number == 5 else varint(number << 3 | 0) + varint(value))


def device_plane(name, ops, stat_names=("program_id", "tf_op", "flops", "jit(o)/gae/copy:")):
    """An XPlane whose event metadata carry ``program_id`` and ``tf_op``: [(hlo text, program, tf_op or None or a ref)]."""
    ids = {n: i for i, n in enumerate(stat_names, 1)}
    body = field(2, name) + field(3, field(2, "XLA Ops"))
    for i, n in enumerate(stat_names, 1):
        body += field(5, varint(1 << 3 | 0) + varint(i) + field(2, varint(1 << 3 | 0) + varint(i) + field(2, n)))
    for key, (hlo, program, tf_op) in enumerate(ops, 1):
        metadata = varint(1 << 3 | 0) + varint(key) + field(2, hlo) + field(5, stat(ids["flops"], 4, 7))
        if program is not None:
            metadata += field(5, stat(ids["program_id"], 3, program))
        if isinstance(tf_op, str):
            metadata += field(5, stat(ids["tf_op"], 5, tf_op))
        elif tf_op is not None:
            metadata += field(5, stat(ids["tf_op"], 7, tf_op))
        body += field(4, varint(1 << 3 | 0) + varint(key) + field(2, metadata))
    return field(1, body)


def test_fields_reads_varints_fixed_widths_and_nested_messages():
    buf = varint(1 << 3 | 0) + varint(300) + field(2, "abc") + varint(3 << 3 | 1) + (7).to_bytes(8, "little") + varint(4 << 3 | 5) + (9).to_bytes(4, "little")
    got = [(n, w, bytes(v) if w == 2 else v) for n, w, v in scopes._fields(memoryview(buf))]
    assert got == [(1, 0, 300), (2, 2, b"abc"), (3, 1, 7), (4, 5, 9)]
    with pytest.raises(ValueError):
        list(scopes._fields(memoryview(bytes([0x0B]))))  # wire type 3: a group, which no profiler proto uses


def test_op_names_come_from_the_event_metadata_of_the_device_planes():
    big = 12719869893039600841  # a program id is a fingerprint: over 63 bits
    space = device_plane("/host:CPU", [("%copy.9 = f32[] copy(x)", 5, "jit(h)/wm.rssm/copy:")]) + device_plane("/device:TPU:0", [
        ("%fusion.1 = bf16[8]{0} fusion(a), kind=kLoop", big, "jit(step)/jit(main)/wm.encoder/dot_general:"),
        ("%copy.2 = bf16[8]{0} copy(b)", big, None),  # no tf_op: a copy the compiler put in
        ("%copy.2 = bf16[4]{0} copy(c)", 7, 4),  # the other program's copy.2; its tf_op kept once, as a stat metadata's name
        ("$core.py:1 f", None, "x"),  # no program: not an op
    ]) + device_plane("/device:TPU:1", [("%while.3 = () while(t)", big, "jit(step)/jit(main)/while:")])
    assert scopes.program_op_names(memoryview(space)) == {
        big: {"fusion.1": "jit(step)/jit(main)/wm.encoder/dot_general", "copy.2": "", "while.3": "jit(step)/jit(main)/while"},
        7: {"copy.2": "jit(o)/gae/copy"},
    }


# ---- arithmetic on events --------------------------------------------------------------------

@pytest.mark.parametrize("op_name, scope", [
    ("jit(fused)/jit(main)/while/body/wm.encoder/WorldModel/encoder/conv_general_dilated", "wm.encoder"),
    ("jit(fused)/jit(main)/while/body/transpose(jvp(wm.rssm))/while/body/dot_general", "wm.rssm"),
    ("jit(fused)/jvp(actor.loss)/jvp(behavior.imagine)/while/body/mul", "behavior.imagine"),  # the innermost wins
    ("jit(fused)/transpose(jvp(actor.loss/behavior.imagine))/while/body/mul", "behavior.imagine"),
    ("actor.optim/jit(_where)/select_n", "actor.optim"),
    ("jit(_lambda_)/replay.write/scatter", "replay.write"),
    ("jit(anakin_phase)/jit(main)/gae/while/body/add", "gae"),
    ("jit(fused)/jit(main)/mygae/add", "unscoped"),  # a component, not a substring
    ("", "unscoped"),
])
def test_scope_of_finds_the_innermost_scope_through_jvp_and_transpose(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_module_of_is_the_program_running_at_that_time():
    modules = [("a(1)", 100, 50), ("b(2)", 200, 100), ("a(1)", 400, 10)]
    assert [scopes.module_of(t, modules) for t in (99, 100, 149, 150, 250, 405, 410)] == [None, "a(1)", "a(1)", None, "b(2)", "a(1)", None]


def test_every_part_of_a_gap_goes_to_the_innermost_span_open_over_it_else_to_the_iteration_else_outside():
    spans = [("iter", 0, 1000), ("rollout", 100, 500), ("env.step", 200, 300), ("update.dispatch", 700, 200), ("iter", 1000, 100)]
    assert scopes.split_gap((250, 450), spans) == {"env.step": 200}  # rollout is open too: the shorter span is the inner one
    assert scopes.split_gap((150, 550), spans) == {"rollout": 100, "env.step": 300}
    assert scopes.split_gap((0, 760), spans) == {"iter": 200, "rollout": 200, "env.step": 300, "update.dispatch": 60}
    assert scopes.split_gap((1050, 1500), spans) == {"iter": 50, "outside": 400}
    assert scopes.split_gap((0, 10), []) == {"outside": 10}


def hand_trace():
    """Two programs on one device, a scan's body inside its `while`, the same op name in both programs."""
    ops = [
        ("fusion.1", 100, 100), ("while.2", 200, 400), ("fusion.3", 220, 100), ("fusion.3", 400, 100), ("copy.4", 600, 50),
        ("copy.4", 1000, 200),  # the other program's copy.4
        ("fusion.9", 1700, 100),  # after the last iteration: clipped away
    ]
    modules = [("jit_train(1)", 100, 560), ("jit_write(2)", 990, 220), ("jit_train(1)", 1690, 200)]
    op_names = {
        "jit_train(1)": {"fusion.1": "jit(train)/replay.gather/gather", "while.2": "jit(train)/while", "fusion.3": "jit(train)/while/body/transpose(jvp(wm.rssm))/dot", "copy.4": ""},
        "jit_write(2)": {"copy.4": "jit(_lambda_)/replay.write/scatter"},
    }
    spans = [("iter", 0, 800), ("exec.train", 50, 100), ("player.sync", 660, 330), ("iter", 800, 800), ("replay.write", 1190, 20)]
    return {"device": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "spans": spans, "op_names": op_names}


def test_reduce_hand_made_scopes_unscoped_and_gaps():
    out = scopes.reduce(hand_trace())
    assert out["window_s"] == pytest.approx(1600e-9) and out["iterations"] == 2
    assert out["busy_s"] == pytest.approx((550 + 200) * 1e-9)
    by_scope = dict(out["by_scope"])
    # while.2 keeps 200 of its 400 (its body's two fusions are taken out) and carries no scope; train's copy.4 has none either
    assert by_scope == {
        "replay.gather": pytest.approx(100e-9), "wm.rssm": pytest.approx(200e-9),
        "replay.write": pytest.approx(200e-9), "unscoped": pytest.approx(250e-9),
    }
    assert sum(by_scope.values()) == pytest.approx(out["busy_s"])
    assert out["unscoped_share"] == pytest.approx(250 / 750)
    assert dict(out["unscoped_ops"]) == {"jit_train: while.2": pytest.approx(200e-9), "jit_train: copy.4": pytest.approx(50e-9)}
    # gaps: 0..100 (exec.train from 50), 650..1000 (player.sync 660..990), 1200..1600 (replay.write until 1210)
    gaps = {name: s for name, s, _, _ in out["idle_gaps"]}
    assert gaps == {
        "exec.train": pytest.approx(50e-9), "player.sync": pytest.approx(330e-9), "replay.write": pytest.approx(10e-9),
        "iter": pytest.approx((50 + 20 + 390) * 1e-9),
    }
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"])


def test_reduce_without_iteration_spans_takes_the_extent_of_the_ops_and_without_ops_nothing():
    trace = hand_trace()
    trace["spans"] = []
    out = scopes.reduce(trace)
    assert out["window_s"] == pytest.approx(1700e-9) and out["iterations"] == 0
    assert {name for name, *_ in out["idle_gaps"]} == {"outside"}
    assert scopes.reduce({"device": {}, "spans": [], "op_names": {}}) is None


def test_gaps_over_a_millisecond_are_counted_and_listed():
    ms = 1_000_000
    trace = {
        "device": {"/device:TPU:0": {"ops": [("a", 0, ms), ("a", 4 * ms, ms), ("a", 5 * ms + 1000, ms)], "modules": [("m(1)", 0, 7 * ms)]}},
        "spans": [("iter", 0, 7 * ms), ("log.flush", ms, 3 * ms)], "op_names": {"m(1)": {"a": "jit(m)/gae/add"}},
    }
    out = scopes.reduce(trace)
    (flush,) = [g for g in out["idle_gaps"] if g[0] == "log.flush"]
    assert flush[1] == pytest.approx(3e-3) and flush[2] == 1 and flush[3] == pytest.approx(3e-3)
    (it,) = [g for g in out["idle_gaps"] if g[0] == "iter"]
    assert it[1] == pytest.approx(1e-3) and it[2] == 0  # a microsecond between two ops, and a tail of 999 us: neither is over a millisecond
    assert out["long_gaps"] == [["log.flush", pytest.approx(1e-3), pytest.approx(3e-3)]]
    assert "log.flush" in scopes.table(out) and "gae" in scopes.table(out)


def test_the_programs_spans_are_known_by_their_stat_on_the_loop_threads_line(tmp_path):
    """A trace recorded here, on the CPU: the annotations the program's tracker enters carry a ``span`` stat,
    JAX's own marks on the same line do not, and a writer thread's span labels no gap of the loop."""
    import threading

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.telemetry import SPANS

    SPANS.reset()
    jax.profiler.start_trace(str(tmp_path))
    SPANS.iteration(3)
    with SPANS.span("update.dispatch"):
        with SPANS.span("exec.some_new_span_no_list_knows", phase=False):
            jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()  # JAX marks this thread's line too
        with jax.profiler.TraceAnnotation("not.the.programs"):
            pass
    beside = threading.Thread(target=lambda: SPANS.pop(SPANS.push("ckpt.snapshot")))
    beside.start()
    beside.join()
    SPANS.end_iteration()
    jax.profiler.stop_trace()
    SPANS.reset()
    trace = scopes.read_xplane(scopes.find_xplane(str(tmp_path)))
    assert [name for name, _, _ in trace["spans"]] == ["iter", "update.dispatch", "exec.some_new_span_no_list_knows"]


# ---- the recorded trace ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def toy():
    return scopes.read_xplane(str(TOY))


def test_recorded_trace_is_read_from_the_profilers_own_file(toy):
    (plane,) = toy["device"]
    assert plane == "/device:TPU:0"
    modules = {name for name, _, _ in toy["device"][plane]["modules"]}
    assert len(modules) == 1 and next(iter(modules)).startswith("jit_step(")
    assert set(toy["op_names"]) == modules  # found by the program id in the module line's event name
    names = toy["op_names"][next(iter(modules))]
    op_short = {name for name, _, _ in toy["device"][plane]["ops"]}
    assert op_short <= set(names)  # every executed op is an instruction of the program's HLO proto
    assert any("wm.rssm" in v for v in names.values()) and any("transpose(jvp(" in v for v in names.values())
    span_names = {name for name, _, _ in toy["spans"]}
    assert span_names == {"iter", "update.dispatch", "exec.step", "log.flush"}
    assert sum(1 for name, _, _ in toy["spans"] if name == "iter") == 6


def test_recorded_trace_scopes_unscoped_a_gap_under_a_span_and_a_gap_under_none(toy):
    out = scopes.reduce(toy)
    by_scope = dict(out["by_scope"])
    # the loss under ``wm.heads`` is fused into the ops of the matmul before it, which no scope holds
    assert set(by_scope) == {"wm.encoder", "wm.rssm", "unscoped"}
    assert by_scope["wm.rssm"] > by_scope["wm.encoder"] > 0  # eight scan steps forward and back against one matmul
    assert 0.0 < out["unscoped_share"] < 1.0
    assert any("jit_step: " in name for name, _ in out["unscoped_ops"])  # the matmul outside any scope
    assert sum(by_scope.values()) == pytest.approx(out["busy_s"], rel=1e-6)
    gaps = {name: (s, count) for name, s, count, _ in out["idle_gaps"]}
    assert gaps["log.flush"][0] > 0.011 and gaps["log.flush"][1] == 3  # 4 ms of sleep under the span, in every second iteration
    assert gaps["iter"][0] > 0.030 and gaps["iter"][1] >= 2  # 12 ms of sleep under no span but the iteration's, in the others
    assert sum(s for s, _ in gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
    assert all(length > 1e-3 for _, _, length in out["long_gaps"])


def test_the_command_prints_the_table(toy, capsys):
    assert scopes.main([str(TOY)]) == 0
    printed = capsys.readouterr().out
    assert "device self time by scope" in printed and "idle gaps by program span" in printed and "wm.rssm" in printed
    assert scopes.main([str(TOY.parent / "nothing_here")]) == 2
