"""The comparison that decides ``correct``: a whole run on the CPU at a
tiny frame size, past the harness's look for a chip, sound and with the
served path broken underneath; and the control."""

import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import control
import run
from repro.runtime.fleet import PixieFleet

ROOT = Path(__file__).resolve().parents[2]
CELLS = ("sobel45-1080p-poisson", "edgechain-1080p-closed64")
SEED = 2**31 + 77


def tiny(cell: str) -> dict:
    spec = run.load_cell(ROOT, cell)
    traffic = spec["traffic"]
    traffic["frames"].update(height=20, width=36, pool=4)
    if traffic["loop"] == "open":
        traffic["rate_per_s"] = 6.0
    else:
        traffic["clients"] = 4
    return spec


def serve(spec: dict) -> dict:
    return run.run_cell(spec, seed=SEED, seconds=1.5, trace=False, chips=1,
                        t_start=time.perf_counter(), log=lambda *_: None)


def break_flush(monkeypatch, fault: str) -> None:
    """Make every fleet flush answer wrongly in one way."""
    flush = PixieFleet.flush

    def broken(self, limit=None):
        frames = {t: p.payload for t, p in self._pending}
        out = flush(self, limit)
        tickets = sorted(out)
        if fault == "altered":
            # One answer altered where it is produced.
            out[tickets[0]] = jnp.asarray(out[tickets[0]]).at[0, 0].add(1)
        elif fault == "half_left_out":
            # Half of the batch not computed; the rest served.
            for t in tickets[::2]:
                out[t] = jnp.zeros_like(jnp.asarray(out[t]))
        elif fault == "unchanged":
            # The datapath hands its input back unchanged.
            for t in tickets:
                out[t] = jnp.asarray(frames[t])
        return out

    monkeypatch.setattr(PixieFleet, "flush", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = serve(tiny(cell))
    assert result["correct"] is True
    assert result["checks"]["wrong_outputs"]["value"] == 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", ("altered", "half_left_out", "unchanged"))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_served_path_is_not_correct(cell, fault, monkeypatch):
    break_flush(monkeypatch, fault)
    result = serve(tiny(cell))
    assert result["correct"] is False
    assert result["checks"]["wrong_outputs"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_int8_fails_and_int16_is_exact_on_8_bit_frames(cell):
    """The nearest narrower type, int16, holds every value these apps
    reach on 0-255 frames (at most 16 * 255), so its answers are the
    reference's; int8 wraps, and the comparison catches it."""
    spec = tiny(cell)
    int8 = control.reference_control(spec, SEED, np.int8, 64)
    int16 = control.reference_control(spec, SEED, np.int16, 64)
    assert int8["compared"] == int16["compared"] == 64
    assert int8["wrong_outputs"] > 0
    assert int16["wrong_outputs"] == 0
