"""The benchmark's own checks (benchmark/selfcheck.py) as tests.

Arithmetic first: BENCHMARK.json against the contract, the FLOP functions
against hand-worked numbers, the trace reduction against the recorded v5e
trace. Then every kind of cell rehearsed at a tiny size on the CPU, each in
a process of its own (nothing here imports JAX or touches a TPU topology),
and the three properties the contract asks of the command: no result
without an accelerator, none in a bare directory, and new files found with
no edit. A rehearsal's numbers are never a device metric: the checks insist
on the `rehearsal_` prefix.
"""

import importlib.util
import os

import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "selfcheck.py")
_spec = importlib.util.spec_from_file_location("_benchmark_selfcheck", _PATH)
selfcheck = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(selfcheck)


@pytest.mark.parametrize("check", selfcheck.ARITHMETIC,
                         ids=lambda fn: fn.__name__)
def test_arithmetic(check):
    check()


def test_rehearsal_spec_contract():
    selfcheck.check_spec_contract(selfcheck.REHEARSAL_SPEC, real=False)


@pytest.mark.parametrize("workload,trace", selfcheck._rehearsal_cells())
def test_rehearsal_cell(workload, trace):
    selfcheck.check_rehearsal_cell(workload, trace)


@pytest.mark.parametrize("check", selfcheck.PROCESSES,
                         ids=lambda fn: fn.__name__)
def test_command(check):
    check()
