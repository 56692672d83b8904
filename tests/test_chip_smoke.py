"""chip_smoke.py's phases at tiny sizes on the CPU: rehearsals 1 and 2
of the on-chip-measurement guide, kept as tests.

The script itself has no CPU mode; its phases are functions that take
their sizes and the platform they must find, so this file calls them
with ``platform="cpu"``, a tiny ResNet, a 2-layer decoder and four of
the eight virtual devices. The Pallas kernels run through the
interpreter, and it is THIS file that routes them there (by wrapping
the two ``ops`` entry points), not an option of the program.
"""

import functools
import importlib
import json
import subprocess
import sys

import pytest

import chip_smoke

fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
pa = importlib.import_module("tensorflowonspark_tpu.ops.paged_attention")

TINY_RESNET = {"stage_sizes": [1, 1], "num_classes": 10, "width": 8}
#: widths no other test file uses: the engine's jitted step functions
#: are cached per model config, and the ones traced here hold
#: interpreter-mode kernels
TINY_LM = {"vocab": 211, "hidden": 48, "num_heads": 3, "num_layers": 2,
           "max_len": 128}


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """What a TPU backend would compile runs in the Pallas interpreter;
    yields the number of traces that went through each entry point."""
    traced = {"flash": 0, "paged": 0}

    def route(module, name, key):
        kernel = getattr(module, name)

        @functools.wraps(kernel)
        def interpreted(*args, **kw):
            traced[key] += 1
            return kernel(*args, force_pallas=True, interpret=True, **kw)

        monkeypatch.setattr(module, name, interpreted)

    route(fa, "flash_attention", "flash")
    route(pa, "paged_attention", "paged")
    return traced


def test_fed_phase_tiny(capsys):
    r = chip_smoke.fed_phase(3, platform="cpu", model=TINY_RESNET,
                             batch=16, image=32, steps=7)
    assert r["records_consumed"] == r["records_fed"] == 8 * 16
    assert r["device"]["platform"] == "cpu"
    assert r["transport"] in ("shm", "queue") and r["transport_probe"]
    assert r["losses"][-1] < r["losses"][0]
    assert r["compile"]["programs"] > 0
    # the phase's own line is the last thing it printed, and is JSON
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == r


def test_multichip_phase_on_four_virtual_devices():
    r = chip_smoke.multichip_phase(3, platform="cpu", model=TINY_RESNET,
                                   batch=16, image=32, steps=7,
                                   n_devices=4)
    assert r["mesh_devices"] == 4 and r["all_reduce_in_step"]
    assert r["layout"]["param_devices"] == 4
    assert [shape for _, shape in r["layout"]["batch_shards"]] \
        == [[4, 32, 32, 3]] * 4
    assert r["loss_rel_diff_max"] <= chip_smoke.MULTICHIP_LOSS_RTOL


def test_serving_phase_tiny(interpreted_kernels):
    r = chip_smoke.serving_phase(5, platform="cpu", model=TINY_LM,
                                 prompt_lens=(5, 32, 70), new_tokens=6)
    assert r["requests"] == 5 and "attn_impl" not in r
    assert r["prefix_hit_rate"] > 0
    assert r["compile"]["programs"] > 0
    # prefill and decode programs of the default engine traced the
    # kernel, and so did both sides of the comparison at its shapes
    assert interpreted_kernels["paged"] >= 4
    versus = r["default_vs_gather"]
    assert versus["q"] == [8, 1, 3, 16] and versus["pool"] == [65, 16, 48]
    assert versus["rel_err"] <= versus["tol"]
    # the repo pins this parity at token level on the CPU
    assert r["parity"] == "tokens equal"


def test_kernel_phase_tiny(interpreted_kernels):
    r = chip_smoke.kernel_phase(7, platform="cpu", batch=2, seq=64,
                                heads=3, head_dim=16, block_size=16,
                                prefill=32)
    names = [c["case"] for c in r["cases"]]
    assert len(names) == 16 and len(set(names)) == 16
    assert {n.split("_")[0] for n in names} == {"flash", "paged"}
    # on the CPU nothing lowers to a TPU kernel; on a chip the phase
    # itself refuses a case that does not
    assert not any(c["tpu_custom_call"] for c in r["cases"])
    assert interpreted_kernels["flash"] and interpreted_kernels["paged"]


def test_phase_refuses_the_wrong_platform():
    with pytest.raises(RuntimeError, match="wanted a 'tpu' device"):
        chip_smoke.kernel_phase(0)


def test_script_has_no_cpu_mode():
    """Run as the driver runs it, here where JAX is held to the CPU:
    non-zero exit, the reason on stderr, no result line."""
    proc = subprocess.run([sys.executable, chip_smoke.__file__],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no CPU mode" in proc.stderr
    assert proc.stdout == ""
