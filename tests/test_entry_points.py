"""The entry points on the CPU: the serving launcher and ``chip_smoke.py``.

The launcher runs its own code path (argument parsing, the repo's mesh
helper, packing, ``ServeEngine``) at the reduced size. ``chip_smoke.py``
must refuse to run without a TPU; its phases run here at the reduced
size in interpret mode, so the script cannot rot between chip runs.
"""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config
from repro.launch import serve as launch_serve
from repro.serve import PackedModelCache, pack_tree_psq

ROOT = pathlib.Path(__file__).resolve().parents[1]
LAUNCH = ["--arch", "tinyllama-1.1b", "--psq-packed", "--reduced",
          "--requests", "3", "--max-new-tokens", "4"]


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


def _tokens(done):
    return {r.uid: list(r.output) for r in done}


class TestLauncher:
    @pytest.fixture(scope="class")
    def one_device(self):
        return launch_serve.main(LAUNCH + ["--mesh", "1,1"])

    def test_one_device_serves_every_request(self, one_device):
        assert len(one_device) == 3
        assert all(len(r.output) == 4 for r in one_device)

    @pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
    def test_data_model_mesh_matches_one_device(self, one_device):
        """``--mesh 2,2``: data-sharded slots and tensor-parallel PSQ
        columns through the repo's mesh helper, token-identical."""
        done = launch_serve.main(LAUNCH + ["--mesh", "2,2"])
        assert _tokens(done) == _tokens(one_device)


class TestChipSmoke:
    def test_refuses_without_tpu(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                              capture_output=True, text=True, env=env,
                              timeout=300)
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout

    def test_refuses_without_the_repo(self, tmp_path):
        (tmp_path / "chip_smoke.py").write_bytes(
            (ROOT / "chip_smoke.py").read_bytes())
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("PYTHONPATH", None)
        proc = subprocess.run([sys.executable, "chip_smoke.py"],
                              cwd=tmp_path, capture_output=True, text=True,
                              env=env, timeout=300)
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout

    @pytest.fixture(scope="class")
    def packed(self, chip_smoke):
        cfg, params = chip_smoke.seeded_params(
            get_config("tinyllama-1.1b").reduced(), "pallas-interpret")
        return cfg, pack_tree_psq(params, cfg.quant, PackedModelCache())

    def test_logits_check(self, chip_smoke, packed):
        cfg, tree = packed
        res = chip_smoke.logits_check(tree, cfg, (2, 16),
                                      backend="pallas-interpret")
        assert res["rel_l2"] <= chip_smoke.REL_L2_TOL
        assert res["argmax_agree"] == 1.0

    def test_layer_check_exercises_skip_flags(self, chip_smoke, packed):
        _, tree = packed
        layer = chip_smoke.first_layer(tree, "mlp/down")
        res = chip_smoke.layer_check(layer, backend="pallas-interpret",
                                     rows=8)
        assert res["zero_block_share"] > 0.0
        assert res["max_rel"] <= chip_smoke.LAYER_REL_TOL

    @pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
    def test_mesh_phase(self, chip_smoke):
        res = chip_smoke.mesh_phase(
            get_config("tinyllama-1.1b").reduced(), backend="reference",
            n_requests=4, prompt_len=(8, 32), new_tokens=4, slots=4,
            max_len=64, logits_batch=(2, 16))
        assert res["packed_layer_devices"] == 4
        assert res["packed_layer_split"] == res["mesh"]["model"] == 2
        assert res["kv_pool_devices"] == 4
        assert res["kv_slot_split"] == res["mesh"]["data"] == 2
        assert res["requests_identical"] == 4
        assert (res["first_step_logits"]["rel_l2"]
                <= chip_smoke.MESH_REL_L2_TOL)
