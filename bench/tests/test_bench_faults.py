"""A whole run with the timed path broken underneath comes out not
correct, once for each fault a one-chip serving cell can have: a step
that returns its state unchanged, half of the batch left out, and a
token altered where it is produced (``bench/faults.py``). No cell here
spans chips, so no exchange between chips can be left out. The same
faults at the cells' own sizes were read on the chip (PERF.md)."""
import pytest

from bench import faults, run
from bench.tests.tiny import tiny_cell
from repro.serving.backend import JAXBackend


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(JAXBackend, "decode_sample", faults.FAULTS[fault])
    res = run.run(tiny_cell("internlm2-chat"), 11, 4.0, trace=False,
                  rehearsal=True)
    # requests were served and read, and what was read is wrong
    assert res["compared"]["max_gap"]["value"] is not None
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct_in_the_batch_cell(fault, monkeypatch):
    monkeypatch.setattr(JAXBackend, "decode_sample", faults.FAULTS[fault])
    res = run.run(tiny_cell("dsmoe16b-batch"), 13, 4.0, trace=False,
                  rehearsal=True)
    assert res["compared"]["p99_gap"]["value"] is not None
    assert not res["correct"], res["compared"]
