"""CPU rehearsal of ``chip_smoke.py``: every phase end to end at a tiny size,
with the Pallas kernels in interpret mode — wrong paths, arguments and
control flow show up here instead of on the chip."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


def test_chip_smoke_phases_rehearse_on_cpu(tmp_path, monkeypatch):
    # reference histogram in uneven feature slices (64 = 24 + 24 + 16)
    monkeypatch.setattr(chip_smoke, "HIST_REF_FEATURES", 24)
    fcfg = chip_smoke.smoke_config(n_t=2, n_trees=2, duplicate_k=2,
                                   max_depth=3, n_bins=16)
    X, y = chip_smoke.make_data(8, seed=0, dataset="photons_mini")
    assert chip_smoke.phase_fit_memory(X, y, fcfg, 2) > 0
    art = chip_smoke.phase_fit(X, y, fcfg, seed=0, ensembles_per_batch=2)
    assert art.n_y == 15 and art.p == 64
    chip_smoke.phase_round_trip(art, str(tmp_path), n=60, seed=0,
                                impl="pallas_interpret")
    chip_smoke.phase_kernel_parity(art, X, y, n=60, seed=0,
                                   impl="pallas_interpret")
    chip_smoke.phase_serve(art, X, y, impl="pallas_interpret",
                           requests=((10, "interactive"), (40, "bulk")),
                           buckets=(8,))


def test_chip_smoke_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
