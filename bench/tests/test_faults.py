"""A run with the timed path broken underneath must come out not correct:
a token altered where the decode chunk or the prefill sampler produces it.
The chip check is skipped (`cpu_device`); the rest of the run is real."""
import numpy as np
import pytest

import run
from conftest import smoke_cell


def _alter_decode(monkeypatch):
    from repro.serve.engine import GenerationEngine

    real = GenerationEngine._run_paged_decode_chunk

    def altered(self, *args):
        toks = np.array(real(self, *args))
        active = np.asarray(args[-1])
        toks[0, active] = (toks[0, active] + 1) % self.cfg.vocab_size
        return toks

    monkeypatch.setattr(GenerationEngine, "_run_paged_decode_chunk", altered)


def _alter_prefill(monkeypatch):
    from repro.serve.engine import GenerationEngine

    real = GenerationEngine._sample_rows

    def altered(self, logits, rids, steps):
        return (np.asarray(real(self, logits, rids, steps)) + 1) % self.cfg.vocab_size

    monkeypatch.setattr(GenerationEngine, "_sample_rows", altered)


@pytest.mark.parametrize("fault", [_alter_decode, _alter_prefill],
                         ids=["decode_token", "prefill_token"])
def test_altered_token_is_not_correct(fault, cpu_device, monkeypatch):
    identity, peak = cpu_device
    fault(monkeypatch)
    line = run.run_cell(smoke_cell("opt"), 21, 2.0, False, identity, peak)
    assert not line["correct"]
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
