"""A new cell is new files and new entries: the tests that read
``BENCHMARK.json``'s entries pass, unedited, on a benchmark with two
more configurations and a cell on each, one of them on four chips
(``conftest.roomy_benchmark``). A rule over the whole benchmark (how
many cells, how many on four chips) lives in ``test_tpubench_spec.py``
and nowhere else."""

import inspect
import os
import re

import pytest

import test_brumby14b_l8
import test_dots3_note_prev_l5_ep8
import test_mla_decode_roofline
import test_openpangu_ultra_l5_ep32
import test_tpubench_families
import test_tpubench_pass_readers
import test_tpubench_program_trace
import test_tpubench_spec

HERE = os.path.dirname(os.path.abspath(__file__))

#: (module, test, arguments): each test that reads the entries
CASES = (
    [(test_tpubench_spec, t, {})
     for t in ("test_configs", "test_workloads", "test_metrics")]
    + [(m, "test_the_cell_is_put_together_from_its_files", {})
       for m in (test_openpangu_ultra_l5_ep32, test_dots3_note_prev_l5_ep8,
                 test_brumby14b_l8)]
    + [(test_tpubench_families,
        "test_every_configuration_checks_at_the_length_it_states", {}),
       (test_tpubench_program_trace,
        "test_the_new_metrics_name_readers_and_arguments_that_exist", {}),
       (test_mla_decode_roofline,
        "test_the_metric_resolves_through_spec_as_the_other_readers_do", {})]
    + [(test_tpubench_pass_readers,
        "test_each_new_metric_is_its_cells_and_reads_its_file", {"name": n})
       for n in test_tpubench_pass_readers.NEW])


@pytest.mark.parametrize(
    "module, test, kwargs", CASES,
    ids=[f"{m.__name__}::{t}" + "".join(f"[{v}]" for v in kw.values())
         for m, t, kw in CASES])
def test_a_structural_test_takes_two_more_cells_unedited(
        module, test, kwargs, roomy_benchmark, monkeypatch):
    # test_tpubench_spec reads the entries once, at import
    monkeypatch.setattr(test_tpubench_spec, "BENCH", roomy_benchmark)
    fn = getattr(module, test)
    if "monkeypatch" in inspect.signature(fn).parameters:
        kwargs = dict(kwargs, monkeypatch=monkeypatch)
    fn(**kwargs)


#: a count of cells or configurations held to a number
_LEN = r"""len\((?:[^()\n]|\(\))*\[["'](?:workloads|configs)["']\]"""
PIN = re.compile(_LEN + r"\s*\)\s*[=!<>]=?\s*\d|\d\s*[=!<>]=?\s*" + _LEN)


def test_no_test_but_the_specs_pins_the_number_of_cells():
    checked = 0
    for f in sorted(os.listdir(HERE)):
        # the spec's tests hold the rules; this file, the grep's examples
        if not f.endswith(".py") or f in ("test_tpubench_spec.py",
                                          os.path.basename(__file__)):
            continue
        with open(os.path.join(HERE, f)) as fh:
            for i, line in enumerate(fh, 1):
                assert not PIN.search(line), f"{f}:{i}: {line.strip()}"
        checked += 1
    assert checked >= 10
    # the grep bites
    assert PIN.search('assert len(bench["workloads"]) == 6')
    assert PIN.search("assert 5 == len(spec.benchmark()['configs'])")
    assert PIN.search('    assert 2 <= len(cells["workloads"]) <= 24')
