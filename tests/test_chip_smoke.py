"""``chip_smoke.py`` on the CPU: its training run at a toy size with the
kernels in interpret mode passes every check but the platform gate, and
without a TPU the script fails before it prints a result."""
import dataclasses
import sys

import chip_smoke
from repro.configs.base import reduced


def test_chip_smoke_training_passes_its_checks_at_toy_size():
    cfg = dataclasses.replace(reduced(chip_smoke.GPT_PAPER), n_layers=2)
    report = chip_smoke.train(cfg, iters=2, global_tokens=256, max_len=64,
                              impl="interpret")
    assert chip_smoke.check_training(report, "interpret") == []
    assert len(report["losses"]) == 2
    # a forward and a backward program per stage for every shape it ran
    assert len(report["stage_programs"]) >= 2 * chip_smoke.N_STAGES
    assert report["compiles"] > 0


def test_chip_smoke_refuses_a_cpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "no TPU" in err
