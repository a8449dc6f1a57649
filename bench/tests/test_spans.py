"""The reduction of program spans and scopes (``bench/spans.py``): on
hand-made intervals, on a hand-made serialized trace and on the small
recorded CPU trace."""
import os

from bench import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")
US = 1e3                                     # trace times are in ns


def test_scope_of_an_op_name():
    assert spans.scope_of(
        "jit(_step)/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/"
        "rematted_computation/repro.attention/dot_general:") == "attention"
    assert spans.scope_of("jit(_step)/transpose(jvp(repro.mlp))/dot") == "mlp"
    assert spans.scope_of(
        "jit(_step)/repro.optimizer/repro.gossip/mul:") == "gossip"
    assert spans.scope_of("jit(_step)/vmap(jvp())/broadcast_in_dim:") is None
    assert spans.scope_of(None) is None


def _made():
    """Two devices, two rounds. Device d0: a ``while`` (no scope) holding
    two attention operations, then an MLP and an unscoped operation;
    d1: one optimizer operation per round."""
    return spans.Spans(
        devices={
            "d0": [(None, 0, 40 * US), ("attention", 5 * US, 15 * US),
                   ("attention", 20 * US, 30 * US), ("mlp", 40 * US, 50 * US),
                   (None, 60 * US, 70 * US), ("attention", 80 * US, 100 * US)],
            "d1": [("optimizer", 0, 30 * US), ("optimizer", 50 * US, 80 * US)],
        },
        host=[("bench.round", {}, 0, 50 * US),
              ("bench.round", {}, 50 * US, 100 * US),
              ("bench.feed", {}, 50 * US, 55 * US),
              ("repro.round.dispatch", {"step": 3}, 52 * US, 58 * US),
              ("repro.round.sync", {"step": 3}, 58 * US, 100 * US),
              ("PjitFunction(step)", {}, 55 * US, 58 * US),
              ("np.asarray(jax.Array)", {}, 72 * US, 78 * US)])


def test_scope_time_per_unit_and_device():
    r = spans.reduce(_made(), "bench.round")
    assert r["units"] == 2 and r["devices"] == 2
    per = r["scope_s_per_unit"]
    # attention: 10 + 10 nested in the while, counted once, + 20 us
    assert abs(per["attention"] - 40e-6 / 4) < 1e-15
    assert abs(per["mlp"] - 10e-6 / 4) < 1e-15
    assert abs(per["optimizer"] - 60e-6 / 4) < 1e-15
    assert list(per) == ["optimizer", "attention", "mlp"]
    # unscoped: the while's own 20 us and the 10 us operation
    assert abs(r["coverage"] - 110 / 140) < 1e-12


def test_program_spans_sums_and_arguments():
    r = spans.reduce(_made(), "bench.round")
    assert set(r["spans"]) == {"repro.round.dispatch", "repro.round.sync"}
    sync = r["spans"]["repro.round.sync"]
    assert sync["count"] == 1 and abs(sync["seconds"] - 42e-6) < 1e-15
    assert sync["calls"] == [[{"step": 3}, sync["seconds"]]]


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def test_op_names_from_a_made_serialized_trace(tmp_path):
    stat = lambda sid, **v: b"".join(
        [_field(1, sid)] + [_field({"s": 5, "r": 7}[k], x) for k, x in v.items()])
    meta = lambda eid, name, *stats: _field(4, _field(1, eid) + _field(
        2, _field(1, eid) + _field(2, name) + b"".join(_field(5, s)
                                                        for s in stats)))
    stat_meta = lambda sid, name: _field(5, _field(1, sid) + _field(
        2, _field(1, sid) + _field(2, name)))
    tpu = (_field(2, "/device:TPU:0") + stat_meta(1, "tf_op")
           + stat_meta(2, "hlo_category")
           + stat_meta(9, "jit(step)/transpose(jvp(repro.mlp))/dot:")
           + meta(1, "%fusion.1 = f32[8] fusion()",
                  stat(2, s="loop fusion"),
                  stat(1, s="jit(step)/repro.attention/dot_general:"))
           + meta(2, "%fusion.2 = f32[8] fusion()", stat(1, r=9))
           + meta(3, "%while.3 = () while()", stat(2, s="while")))
    host = _field(2, "/host:CPU") + stat_meta(1, "tf_op") + meta(
        1, "dot.1", stat(1, s="jit(step)/repro.logits/dot:"))
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(_field(1, tpu) + _field(1, host) + _field(2, "no error"))
    names = spans.op_names(str(path))
    assert names == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(step)/repro.attention/dot_general:",
        "%fusion.2 = f32[8] fusion()": "jit(step)/transpose(jvp(repro.mlp))/dot:"}}
    assert spans.op_names(str(path), device_plane="/host:CPU") == {
        "/host:CPU": {"dot.1": "jit(step)/repro.logits/dot:"}}


def test_the_recorded_cpu_trace_has_no_scopes():
    sp = spans.load(DATA, device_plane="/host:CPU", ops_line="tf_XLA")
    assert sp.devices["/host:CPU"]
    assert all(sc is None for sc, _, _ in sp.devices["/host:CPU"])
    r = spans.reduce(sp, "bench.round")
    assert r["units"] == 4 and r["coverage"] == 0.0
    assert r["scope_s_per_unit"] == {} and r["spans"] == {}


def test_a_trace_with_no_unit_span_is_an_error():
    try:
        spans.reduce(spans.Spans(devices={"d0": [(None, 0, 1)]}), "bench.round")
    except ValueError:
        return
    raise AssertionError("no unit span must not reduce")
