"""The bytes functions against hand-computed shapes, and the trace
reduction on a hand-made trace and on a small recorded one."""

import gzip
import json
import os

import pytest

from harness import bytes_fns, peaks, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def test_paged_read_bytes_by_hand():
    # InternLM2-1.8B: 24 layers, 8 KV heads, 16 Q heads of 128, bf16.
    # One live token's K and V in one layer: 2 * 8 * 128 * 2 B = 4096 B.
    # 1,000 live tokens over 10 rows: 24 * (1000 * 4096 + 2 * 10 * 16*128*2)
    assert bytes_fns.paged_read_bytes(1000, 10, 24, 8, 16, 128) == \
        24 * (4_096_000 + 81_920)
    # 96 KiB a token over all layers, as the configuration's notes say
    assert bytes_fns.paged_read_bytes(1, 0, 24, 8, 16, 128) == 96 * 1024


def test_paged_write_bytes_by_hand():
    assert bytes_fns.paged_write_bytes(96, 24, 8, 128) == 96 * 96 * 1024
    # int8 pool: half
    assert bytes_fns.paged_write_bytes(96, 24, 8, 128, 1) == 96 * 48 * 1024


def test_weight_bytes_by_hand():
    dims = {"D": 2048, "H": 16, "Hkv": 8, "dh": 128, "F": 8192, "L": 24,
            "V": 92544}
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert bytes_fns.weight_bytes(dims) == 2 * (24 * per_layer + 2048 * 92544)


def test_an_unknown_device_kind_is_an_error():
    assert peaks.of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        peaks.of("TPU v9 imaginary")


def test_compact_names():
    read = ("%closed_call.41 = bf16[96,8,2,128]{3,2,1,0:T(2,128)(2,1)S(1)} "
            "custom-call(s32[1]{0:T(128)} %bitcast.171, s32[96,16]{1,0} %g)")
    assert tracered.compact(read) == "closed_call.41|custom-call|bf16[96,8,2,128]"
    loop = ("%while.36 = (s32[]{:T(128)}, bf16[24,769]{1,0:T(8,128)(2,1)}) "
            "while((s32[]{:T(128)}, bf16[2]{0}) %tuple.145), condition=%c")
    assert tracered.parts(tracered.compact(loop))[1] == "while"
    assert tracered.compact("jit_decode(123)") == "jit_decode(123)"


def _hand_made():
    """Two chips. Chip 0: a decode program [0, 100) ns holding a `while`
    [0, 100) whose body is a write kernel [10, 20), a read kernel [20, 60)
    and an all-reduce [60, 70); then a gap and a lone fusion [150, 200).
    Chip 1: one fusion [0, 100)."""
    ops0 = [["while.1|while|(s32[])", 0, 100],
            ["closed_call.1|custom-call|(bf16[2,8], bf16[2,8])", 10, 10],
            ["custom-call.5|custom-call|s32[4]", 12, 1],
            ["closed_call.2|custom-call|bf16[4,2]", 20, 40],
            ["all-reduce.3|all-reduce|bf16[4]", 60, 10],
            ["fusion.9|fusion|bf16[4]", 150, 50]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_decode(1)", 0, 100]]},
            {"name": "XLA Ops", "events": ops0}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["fusion.9|fusion|bf16[4]", 0, 100]]}]},
        {"name": "/host:CPU", "lines": [{"name": "", "events": [["x", 0, 5]]}]}]}


def test_reduction_of_a_hand_made_trace():
    red = tracered.reduce(_hand_made())
    assert red["devices"] == 2
    # chip 0 is busy [0, 100) and [150, 200) of [0, 200); chip 1 100 of 100
    assert red["busy_s"] == pytest.approx((150 + 100) / 2 / 1e9)
    assert red["window_s"] == pytest.approx((200 + 100) / 2 / 1e9)
    kernels = red["kernels"]
    assert kernels["read"] == {"seconds": pytest.approx(40 / 2 / 1e9), "calls": 0.5}
    assert kernels["write"] == {"seconds": pytest.approx(10 / 2 / 1e9), "calls": 0.5}
    assert kernels["decode"]["seconds"] == pytest.approx(100 / 2 / 1e9)
    names = [name for name, _ in red["device_ops"]]
    assert not any("while" in name for name in names)     # containers left out
    assert names[0].startswith("fusion.9")                # 50 + 100 ns
    assert red["idle_gaps"][0] == ["unattributed", pytest.approx(50 / 1e9)]
    assert tracered.reduce({"planes": []}) == {"devices": 0}


def test_reduction_of_the_recorded_trace():
    """benchmark/tests/data/trace_decode_closed.json.gz: the first part of a
    traced window of internlm2-1.8b.decode-closed on one v5e (PR 23). Busy
    time is checked against a plain sweep over the interval ends."""
    with gzip.open(os.path.join(HERE, "data", "trace_decode_closed.json.gz"),
                   "rt") as fp:
        trace = json.load(fp)
    red = tracered.reduce(trace)
    assert red["devices"] == 1
    events = tracered.line_of(trace["planes"][0], "XLA Ops")
    ends = sorted({e[1] for e in events} | {e[1] + e[2] for e in events})
    spans = sorted((e[1], e[1] + e[2]) for e in events)
    busy, i, open_until = 0, 0, -1
    for a, b in zip(ends, ends[1:]):
        while i < len(spans) and spans[i][0] <= a:
            open_until = max(open_until, spans[i][1])
            i += 1
        if open_until > a:
            busy += b - a
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert 0.5 < red["busy_s"] / red["window_s"] <= 1.0
    kernels = red["kernels"]
    # one read and one write kernel call a layer a step: 16 steps, 24 layers
    assert kernels["read"]["calls"] == kernels["write"]["calls"] == 16 * 24
    assert kernels["read"]["seconds"] > kernels["write"]["seconds"] > 0
    assert kernels["decode"]["seconds"] >= (kernels["read"]["seconds"]
                                            + kernels["write"]["seconds"])
    assert red["modules"]["jit_decode"]["count"] >= 1
