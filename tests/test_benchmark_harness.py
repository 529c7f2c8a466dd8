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
