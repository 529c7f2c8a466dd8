"""The benchmark's own CPU-only, sub-second tests, collected here so that
the tier-1 floor guards them (tier-1 collects tests/ only; PERF.md section 7
asked for this since PR 26, whose kind could not touch tests/). Each case
below is one test function of benchmark/tests, run as it stands: byte and
shape facts of both families, trace reduction and kernel names, traffic and
stats, the check's arithmetic, the grep test that keeps the general files
free of any family's name, the weights' digest. The `run.py --tiny`
subprocess tests, the seeded fault runs and the load generator against a
stalled port stay where they are (`python -m pytest benchmark/tests`)."""

import importlib.util
import inspect
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for path in (os.path.dirname(BENCH), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

# module -> the functions left where they are, and why
LEFT = {
    "test_bytes_and_trace": {},
    "test_hostspans": {
        # it pins BENCHMARK.json's per_layer list as PR 24 left it: its four
        # metrics the LAST four, `loop_other_share_pct` in exactly two
        # cells. No PR can append a metric or a cell beside it, and no PR
        # but a `benchmark` one may edit it: what it guards is held below
        # (`test_the_span_metrics_are_declared_once_with_their_cells`) until
        # the next `benchmark` PR loosens it there (PERF.md section 7,
        # first item)
        "test_the_new_metrics_are_declared_as_their_readers_say":
            "pins the list's end and a count of cells"},
    "test_traffic_and_stats": dict.fromkeys((
        "test_open_loop_times_from_due_on_a_stalled_server",
        "test_open_loop_counts_what_it_could_not_send_as_failed",
        "test_closed_loop_opens_when_every_client_has_an_answer"),
        "seconds against a stalled port"),
    "test_data_driven": dict.fromkeys((
        "test_a_cell_a_mix_and_a_metric_are_added_as_new_files",
        "test_a_family_is_added_as_new_files",
        "test_without_the_program_there_is_no_result"), "subprocess runs"),
    "test_check": dict.fromkeys((
        "test_the_sound_program_is_correct",
        "test_an_int8_page_pool_is_not_correct",
        "test_one_layers_weights_off_is_not_correct",
        "test_a_page_table_off_by_one_is_not_correct",
        "test_one_slots_page_table_off_by_one_is_not_correct",
        "test_a_token_altered_where_it_is_produced_is_not_correct",
        "test_the_reference_at_int8_fails_the_limits_the_program_passes",
        "test_the_reference_agrees_with_the_programs_forward"),
        "whole runs at --tiny size"),
    "test_nemotron_h": dict.fromkeys((
        "test_the_new_cell_is_correct_at_tiny_size",
        "test_a_recurrent_state_in_bfloat16_is_not_as_stated",
        "test_one_slots_page_table_off_by_one_is_not_correct"),
        "whole runs at --tiny size"),
    "test_mla_moe": dict.fromkeys((
        "test_the_new_cell_is_correct_at_tiny_size",
        "test_a_latent_plane_in_bfloat16_is_not_as_stated",
        "test_one_slots_page_table_off_by_one_is_not_correct",
        "test_one_layers_weights_off_is_not_correct"),
        "whole runs at --tiny size"),
    "test_afmoe": dict.fromkeys((
        "test_the_new_cell_is_correct_at_tiny_size",
        "test_a_pool_of_either_group_in_bfloat16_is_not_as_stated",
        "test_one_slots_page_table_off_by_one_in_a_group_is_not_correct",
        "test_a_fault_in_the_block_is_not_correct"),
        "whole runs at --tiny size"),
    "test_mla_moe_hc": dict.fromkeys((
        "test_a_phi_in_bfloat16_is_not_as_stated",
        "test_a_fault_in_the_program_is_not_correct"),
        "whole runs at --tiny size"),
    "test_kda_moe": {
        **dict.fromkeys((
            "test_the_new_cell_is_correct_at_tiny_size",
            "test_a_fault_in_the_program_is_not_correct"),
            "whole runs at --tiny size"),
        # as test_hostspans' above: it asserts that solar's cell ENDS every
        # list it is in, so no PR can append a cell beside it, and no PR
        # but a `benchmark` one may edit it; what it guards is held below
        # (`test_solars_cell_follows_nemotrons_where_it_reports`)
        "test_the_cell_reports_what_nemotrons_cell_reports_but_its_kernel":
            "pins the lists' ends"},
    "test_sparse_linear": dict.fromkeys((
        "test_the_new_cell_is_correct_at_tiny_size",
        "test_a_fault_in_the_program_is_not_correct"),
        "whole runs at --tiny size"),
}


def _module(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_tests_{name}", os.path.join(BENCH, "tests", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cases():
    for name, left in LEFT.items():
        module = _module(name)
        for attr, fn in sorted(vars(module).items()):
            if (not attr.startswith("test_") or attr in left
                    or not inspect.isfunction(fn)):
                continue
            wanted = list(inspect.signature(fn).parameters)
            marks = [m for m in getattr(fn, "pytestmark", [])
                     if m.name == "parametrize"]
            if not wanted:
                yield pytest.param(fn, (), id=f"{name}::{attr}")
            elif len(marks) == 1 and marks[0].args[0] == wanted[0]:
                for value in marks[0].args[1]:
                    yield pytest.param(fn, (value,),
                                       id=f"{name}::{attr}[{value}]")
            else:       # wants fixtures: it runs a whole cell
                raise AssertionError(f"{name}::{attr} takes {wanted}: list "
                                     f"it under LEFT or give it no fixture")


@pytest.mark.parametrize("fn,args", list(_cases()))
def test_the_benchmarks_own_case(fn, args):
    fn(*args)


def test_the_cases_left_out_exist():
    """A renamed benchmark test must not drop out of either list."""
    for name, left in LEFT.items():
        have = vars(_module(name))
        assert all(attr in have for attr in left), name


def test_the_span_metrics_are_declared_once_with_their_cells():
    """What benchmark/tests/test_hostspans.py's pin is there for, in the form
    that lets a later PR append: each of PR 24's four span metrics is
    declared once, after the metrics it followed, in the cells its reader
    finds something in (a closed-loop cell has no queue to wait in)."""
    data = _module("test_hostspans").data
    names = [m["name"] for m in data.benchmark_json()["per_layer"]]
    cells = {m["name"]: m["workloads"]
             for m in data.benchmark_json()["per_layer"]}
    open_loop = ["internlm2-1.8b.chat-open"]
    for name, first in (("loop_other_share_pct",
                         ["internlm2-1.8b.decode-closed"] + open_loop),
                        ("pickup_wait_p95_ms", open_loop),
                        ("parked_wait_p95_ms", open_loop),
                        ("prefill_wait_p95_ms", open_loop)):
        assert names.count(name) == 1
        assert names.index(name) > names.index("decode_step_dev_ms")
        assert cells[name][:len(first)] == first


def test_solars_cell_follows_nemotrons_where_it_reports():
    """What benchmark/tests/test_kda_moe.py's pin is there for, in the form
    that lets a later PR append: solar's cell is in every list nemotron's
    decode-closed cell is in (`ssm_update_roofline` apart), after it, and
    its traffic is decode-closed's at 256 clients."""
    data = _module("test_hostspans").data
    nemotron = "nemotron-3-nano-30b-a3b-ep2.decode-closed"
    solar = "solar-open2-250b-ep8.decode256-closed"
    bench = data.benchmark_json()
    for metric in bench["per_layer"] + bench["end_to_end"]:
        cells = metric.get("workloads")
        if cells is None or metric["name"] == "ssm_update_roofline":
            continue
        if nemotron in cells:
            assert cells.index(solar) > cells.index(nemotron), metric["name"]
    assert [w["chips"] for w in bench["workloads"]
            if w["name"] == solar] == [1]
    mix = data.load_cell(solar)["mix"]
    base = data.load_cell("internlm2-1.8b.decode-closed")["mix"]
    assert (mix["clients"], mix["grid"]) == (256, 256)
    assert all(mix[k] == base[k] for k in ("prompt_tokens", "output_tokens",
                                           "ramp", "loop", "sharing"))


# -- what a prompt met on the device's queue (ISSUE 37) ------------------------
EVERY = ["internlm2-1.8b.decode-closed", "internlm2-1.8b.chat-open",
         "nemotron-3-nano-30b-a3b-ep2.decode-closed",
         "joyai-llm-flash-ep8.longprompt-closed",
         "trinity-large-preview-ep8.mixedlen-closed",
         "xing4.0-29b-a4b-ep8.decode-closed",
         "solar-open2-250b-ep8.decode256-closed",
         "minicpm-sala-pp4.longctx-closed"]
OPEN = ["internlm2-1.8b.chat-open"]
# metric -> (its cells, what it moves, the loop it reads, its layer)
QUEUE_METRICS = {
    "dispatch_hold_p95_ms": (OPEN, "ttft_p95_ms", "open", "engine loop"),
    "prefill_ahead_steps_mean": (OPEN, "ttft_p95_ms", "open",
                                 "step programs"),
    "decode_overrun_share_pct": (EVERY, "out_tok_s", None, "engine loop"),
    # how long a decode block is (ISSUE 47)
    "decode_block_steps_mean": (OPEN, "ttft_p95_ms", "open", "engine loop"),
    "prefill_dev_ms_per_call": (EVERY, "out_tok_s", None, "step programs"),
    "prefill_dev_share_pct": (EVERY, "out_tok_s", None, "step programs"),
}


def _queue_run(loop="open"):
    """A run as serve.measure and run.py leave it, as far as the five
    readers look: 21 requests due in the window with the recorder's
    stamps, and a reduced trace of one chip."""
    records = [{"index": i, "due": 1.0 + i, "t_send": 1.0 + i}
               for i in range(21)]
    requests = [{"trace_id": format(i + 1, "032x"), "enqueued_at": 10.0,
                 "granted_at": 10.5, "admitted_at": 10.5 + 0.001 * i,
                 "first_token_at": 11.0, "finished_at": 12.0,
                 "ahead_steps": 8 * (i % 4), "ahead_prefills": i % 2,
                 "generated": 41, "overrun_steps": 10}
                for i in range(21)]
    trace = {"devices": 1, "window_s": 5.0, "busy_s": 4.9, "modules": {
        "jit_prefill__512x1": {"busy_s": 0.3, "count": 30},
        "jit_prefill__llama_paged_prefix_128x4_NP8": {"busy_s": 0.2,
                                                      "count": 10},
        "jit_decode__x16_NP16": {"busy_s": 4.4, "count": 40}}}
    # ten turns of a full block and thirty of a half, two of them with a
    # second block enqueued, and the prefill reads between them
    steps = ([{"phase": "decode",
               "dispatches": {"decode": 1, "decode_steps": 16}}] * 10
             + [{"phase": "decode",
                 "dispatches": {"decode": 1, "decode_steps": 8}}] * 28
             + [{"phase": "decode", "dispatches": {
                 "decode": 2, "decode_steps": 16, "prefill": 1}}] * 2
             + [{"phase": "prefill", "dispatches": {}}] * 5)
    return {"result": {"records": records}, "requests": requests,
            "loaded": {"mix": {"loop": loop}}, "t_open": 0.0,
            "t_close": 100.0, "steps": steps, "trace": trace}


def _without_block_steps(run):
    """The parent's step records: the blocks, not their steps."""
    return {**run, "steps": [
        {**row, "dispatches": {k: v for k, v in row["dispatches"].items()
                               if k != "decode_steps"}}
        for row in run["steps"]]}


def _without(run, *keys):
    return {**run, "requests": [{k: v for k, v in rec.items()
                                 if k not in keys}
                                for rec in run["requests"]]}


@pytest.mark.parametrize("name,value,absent", [
    # 95th percentile of 0 .. 20 ms is 19
    ("dispatch_hold_p95_ms", 19.0, lambda run: _without(run, "granted_at")),
    # 8 x (0, 1, 2, 3, ...) over 21 requests
    ("prefill_ahead_steps_mean", 8 * 30 / 21,
     lambda run: _without(run, "ahead_steps")),
    # 10 of 40 + 10 row-steps a request
    ("decode_overrun_share_pct", 20.0,
     lambda run: _without(run, "overrun_steps")),
    # 10 x 16 + 28 x 8 + 2 x 16 steps in 10 + 28 + 4 blocks
    ("decode_block_steps_mean", 416 / 42, _without_block_steps),
    # 0.5 s in 40 calls; of a 5 s window
    ("prefill_dev_ms_per_call", 12.5, lambda run: {**run, "trace": None}),
    ("prefill_dev_share_pct", 10.0, lambda run: {**run, "trace": None}),
])
def test_a_queue_reader_reads_its_input_and_nothing_without_it(name, value,
                                                               absent):
    data = _module("test_hostspans").data
    read = data.layer_metrics()[name].read
    run = _queue_run()
    assert read(run) == pytest.approx(value)
    # the parent commit's program, an untraced run: nothing, no error
    assert read(absent(run)) is None
    if name.startswith("prefill_dev"):
        run["trace"]["modules"] = {"jit_decode__x16_NP16":
                                   {"busy_s": 4.4, "count": 40}}
        assert read(run) is None
    elif name == "decode_overrun_share_pct":
        # a request still decoding at the window's close has no finish
        assert read(_without(run, "finished_at")) is None


@pytest.mark.parametrize("name", sorted(QUEUE_METRICS))
def test_a_queue_metric_is_declared_once_as_its_reader_says(name):
    data = _module("test_hostspans").data
    cells, moves, loop, layer = QUEUE_METRICS[name]
    declared = [m for m in data.benchmark_json()["per_layer"]
                if m["name"] == name]
    assert len(declared) == 1
    module = data.layer_metrics()[name]
    assert declared[0]["workloads"] == cells
    assert (module.MOVES, getattr(module, "LOOP", None), module.LAYER) \
        == (moves, loop, layer)
    assert (declared[0]["moves"], declared[0]["layer"], declared[0]["unit"],
            declared[0]["source"], declared[0]["better"]) \
        == (module.MOVES, module.LAYER, module.UNIT, module.SOURCE,
            module.BETTER)
    for cell in cells:
        loaded = data.load_cell(cell)
        assert moves in loaded["cell"]["end_to_end"]
        assert loop in (None, loaded["mix"]["loop"])
