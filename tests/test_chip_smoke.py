"""chip_smoke.py's phases at a toy size on the CPU (interpret-mode kernels):
the control flow, the audits and every check the chip run relies on are
exercised in tier-1, so a chip call is spent on the chip's own questions.
The script itself must refuse to report a pass without an accelerator."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(vocab=64, d_model=32, heads=2, layers=2, t_max=512,
                       long_prompt=256, shared_prefix=64, num_slots=8,
                       block=4, page_size=16, gen_div=4, train_batch=4,
                       train_t=256, train_steps=5, lr=1e-2, kernel_batch=2)


@pytest.fixture
def cpu_pallas_attention():
    """The default attention provider registers for the TPU only; put the
    same helper on the CPU (interpret mode) for the duration of a test."""
    from deeplearning4j_tpu.kernels.pallas_attention import \
        register_pallas_flash_attention
    from deeplearning4j_tpu.nn import helpers
    snap = helpers.snapshot_helper("attention")
    register_pallas_flash_attention(platforms=("cpu",))
    yield
    helpers.restore_helper("attention", snap)


def test_request_mix_is_what_the_phases_assume():
    reqs = chip_smoke.make_requests(chip_smoke.FULL)
    lens = [len(p) for p, _, _ in reqs]
    assert len(reqs) == 16 and min(lens) >= 8 and max(lens) == 512
    assert lens.count(512) == 1
    assert all(32 <= n <= 64 for _, n, _ in reqs)
    shared = [p for p, _, _ in reqs if len(p) in (256 + 40, 256 + 17)]
    assert len(shared) == 2
    assert (shared[0][:256] == shared[1][:256]).all()


def test_phases_pass_at_toy_size_on_cpu(cpu_pallas_attention):
    kernels = chip_smoke.phase_kernels(TOY, require_mosaic=False)
    assert set(kernels) == {"short-T@256", "short-T@256/masked"}
    net = chip_smoke.build_net(TOY)
    mosaic = chip_smoke.phase_serve(net, TOY, require_mosaic=False)
    # interpret mode lowers to plain HLO: nothing may CLAIM a Mosaic call
    assert mosaic and not any(mosaic.values())
    losses = chip_smoke.phase_train(net, TOY, require_mosaic=False)
    assert len(losses) == TOY.train_steps
    multi = chip_smoke.phase_multichip(net, TOY, require_mosaic=False)
    assert multi["decode_readback_shards"] == 4   # conftest: 8 devices


def test_missing_kernel_fails_the_phase(cpu_pallas_attention):
    """A phase that must see the Mosaic call fails when it is absent (as
    it is on the CPU) — the check is live, not decorative."""
    net = chip_smoke.build_net(TOY)
    with pytest.raises(chip_smoke.SmokeFailure, match="WITHOUT"):
        chip_smoke.phase_train(net, TOY, require_mosaic=True)


def test_main_refuses_the_cpu():
    """The script, run as the driver runs it, on a machine with no
    accelerator: non-zero exit and no result line."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, os.path.join(root, "chip_smoke.py")],
                       cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "platform cpu" in r.stdout and '"ok"' not in r.stdout
