import pytest

import tiny
from bench import work


def test_v5e_peaks_from_the_table():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_kind_raises(kind):
    with pytest.raises(work.UnknownDevice):
        work.peaks(kind)


def test_useful_work_counts_tau_times_d():
    import json

    import numpy as np

    from bench import models

    rows = [{"tau": np.array([3, 2]), "d": np.array([10, 5])}] * 2
    assert work.sample_steps(rows) == 2 * (30 + 10)
    cfg = json.loads((tiny.BENCH / "configs" / "mel_mnist_k20.json").read_text())
    assert work.flops(models.load(cfg), cfg, 1) == 6 * 280934
