"""Table 2 reproduction: per-component accuracy + ε-swept cascade
accuracy/speedup on the synthetic difficulty-structured dataset.

Small-scale (CPU) variant of examples/paper_reproduction.py so that
``python -m benchmarks.run`` is self-contained.
"""
import time

import numpy as np

from repro.core.resnet_trainer import evaluate_tradeoff, train_backtrack
from repro.data.synth_images import make_image_splits
from repro.models.resnet import CIResNet

EPSILONS = [0.0, 0.01, 0.02, 0.04, 0.20]


def run():
    train, val, test = make_image_splits(n_classes=10, n_train=2048,
                                         n_val=512, n_test=1024, seed=11)
    model = CIResNet(n_blocks=1, n_classes=10, enhance_dim=64)
    t0 = time.time()
    report = train_backtrack(model, train, n_epochs=3, batch_size=128,
                             augment=False, test=test)
    train_s = time.time() - t0
    rows = []
    for m, acc in enumerate(report.component_acc):
        rows.append((f"table2/acc_M{m}", train_s * 1e6 / 3, f"{acc:.4f}"))
    sweep = evaluate_tradeoff(model, report.params, report.state, val, test,
                              EPSILONS, 10)
    for eps, res in sweep:
        rows.append((f"table2/eps={eps:g}/accuracy", 0.0,
                     f"{res.accuracy:.4f}"))
        rows.append((f"table2/eps={eps:g}/speedup", 0.0,
                     f"{res.speedup:.3f}"))
    return rows
