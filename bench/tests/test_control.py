"""The control: the reference one precision below the configuration's
(one bf16 pass, against the stated three) in the program's place must
come out not correct under each cell's limits.

On the chip ``python3 -m bench.control`` reads it at the cells' own
sizes; here it runs at a size a test run holds, where fewer, wider
quantizer steps make every flip count more (a smaller d_model gives
larger 1/sqrt(K) weight steps), so the control reads no lower than on
the chip."""
import numpy as np
import pytest

from bench import cells, compare
from bench.models import dense_psq as M
from bench.tests.tiny import tiny_config

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]


@pytest.fixture(scope="module")
def control_reading():
    cfg = tiny_config()
    w = M.draw(cfg, 2147483901)
    rng = np.random.default_rng(3)
    vocab = cfg["vocab_size"]
    reqs = [{"prompt": rng.integers(0, vocab, 40).astype(np.int32),
             "output": [int(t) for t in rng.integers(0, vocab, 200)]}
            for _ in range(2)]
    return compare.readings(M, cfg, w, reqs, control=True)["control_bf16"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(control_reading, cell):
    checks = compare.verdict(control_reading, 0, compare.limits(cell))
    assert not compare.passed(checks), checks
