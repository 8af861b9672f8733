"""Operation and byte counts against hand counts for DNN10
(30-256-256 | 128-128-64-64-32-32-16-3, split after layer 2), through
the ``mlp`` model kind."""
import json

import numpy as np

from conftest import BENCH, ROOT
import flops
import run as harness

SPLITME, FEDAVG = (json.loads((BENCH / "configs" / f"{n}.json").read_text())
                   for n in ("splitme-dnn10-m50", "fedavg-dnn10-m50"))
MLP = harness.load_kind(ROOT, "mlp")


def test_weight_counts_by_hand():
    model = SPLITME["model"]
    sizes = MLP.sizes(model)
    # 30*256 + 256*256 on the client; 256*128 + 128*128 + 128*64 + 64*64
    # + 64*32 + 32*32 + 32*16 + 16*3 on the server
    assert MLP.forward_flops(model, "client") == 2 * 73_216
    assert flops.weights(sizes["server_dims"]) == 65_072
    assert MLP.forward_flops(model, "full") == 2 * 138_288
    biases = 256 + 256 + 128 + 128 + 64 + 64 + 32 + 32 + 16 + 3
    assert sizes["full_params"] == 138_288 + biases == 139_267
    assert sizes["client_params"] == 73_216 + 256 + 256
    assert sizes["inverse_params"] == 65_072 + biases - 512 - 3 + 256
    assert sizes["split_width"] == 256 and sizes["n_classes"] == 3


def test_fedavg_round_by_hand():
    # per sample: forward 2 * 138,288; backward 2 * 138,288 for the weight
    # gradients and 2 * (138,288 - 30*256) for the input gradients
    per_sample = 276_576 + 276_576 + 261_216
    a = np.zeros((1, 50))
    a[0, :10] = 1
    got = flops.train_flops(MLP, FEDAVG, a, np.array([10]), n_seeds=3)
    assert got == 3 * 10 * 10 * 32 * per_sample


def test_splitme_round_by_hand():
    c_fwd, i_fwd = 2 * 73_216, 2 * 65_072
    c_bwd = 2 * 73_216 + 2 * (73_216 - 30 * 256)
    i_bwd = 2 * 65_072 + 2 * (65_072 - 3 * 16)
    a = np.zeros((2, 50))
    a[0, :4], a[1, :7] = 1, 1
    E = np.array([20, 6])
    steps = 4 * 20 + 7 * 6
    want = steps * 32 * (c_fwd + c_bwd + i_fwd + i_bwd) \
        + (4 + 7) * 96 * (i_fwd + c_fwd)
    assert flops.train_flops(MLP, SPLITME, a, E, n_seeds=1) == want


def test_kl_and_gram_work_by_hand():
    a = np.zeros((1, 50))
    a[0, :5] = 1
    ops, nbytes = flops.kl_work(MLP, SPLITME, a, np.array([6]), n_seeds=2)
    calls = 2 * 5 * 6 * 2                       # phases x clients x E x seeds
    assert nbytes == calls * (2 * 32 * 256 * 4 + 32 * 4)
    assert ops == calls * 16 * 2 * 32 * 256
    ops, nbytes = flops.gram_work(MLP, SPLITME, n_evals=1)
    rows = 50 * 96
    s = (256, 128, 128, 64, 64, 32, 32, 16, 3)
    want = sum(2 * rows * (s[l] + 1) * d2
               for l in range(8) for d2 in (s[l] + 1, s[l + 1]))
    assert ops == want
    t, bound = flops.roofline_seconds(ops, nbytes,
                                      {"bf16_tflops": 197.0,
                                       "hbm_gb_s": 819.0})
    assert bound in ("compute", "memory") and t > 0
