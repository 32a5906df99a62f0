"""chip_smoke.py off the chip: every phase at tiny sizes on the CPU mesh
(so a wrong path, argument or mesh costs no chip time), then ``main``
itself, which on the CPU must refuse to report a result."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

from torchmpi_tpu.models import llama

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

# A width-0.25 ResNet-18 and llama.tiny(), float32: the checks the phases
# make themselves (bit-equal legs, falling loss, flash against full, four
# devices against one) hold here with room to spare.
ENGINE = dict(depth=18, width=0.25, n_classes=10, image=16, batch=8, steps=3,
              dtype=jnp.float32, seed=0, lr=0.02)
LLAMA = dict(cfg=llama.tiny(), steps=2, dtype=jnp.float32, seed=0, lr=0.05)


def test_phase_engine(devices):
    out = chip_smoke.phase_engine(devices[:1], timing_steps=2, **ENGINE)
    assert set(out["losses"]) == {"streamed", "resident", "cast", "repeated"}
    assert out["ms_block_until_ready"] > 0 and out["ms_float_loss"] > 0
    assert out["peak_bytes"] is None        # the CPU backend reports none


def test_phase_kernels(devices):
    out = chip_smoke.phase_kernels(devices[:1], seq=32, cmp_seq=32, **LLAMA)
    assert out["kernel_calls"] == 0         # interpret mode: main refuses it
    assert out["flash_full_loss_diff"] < 1e-5


def test_phase_cross_chip(devices):
    out = chip_smoke.phase_cross_chip(
        devices[:4], engine=ENGINE, llama_sizes=dict(batch=2, seq=32, **LLAMA),
        payloads=(64,), ring_elems=1024)
    assert set(out["kernel_calls"]) == {"dp1:flash", "dp2xtp2:flash",
                                        "dp1xsp4:ring"}
    assert out["engine_loss_diff"] < 1e-4 and out["ring_diff"] < 1e-5


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_the_cpu(argv, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main(argv) != 0
    assert '"ok": true' not in capsys.readouterr().out
