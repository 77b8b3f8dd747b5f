"""A reduced-width stand-in for a cell, small enough for a CPU test run.

The same code path as the cell; only the sizes are cut: ResNet widths
8/16 at depth 1/1, 16x16 images, 6 clients with 3 a round, batch 8,
2 local steps.
"""
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MODEL = {"widths": [8, 16], "depths": [1, 1], "groups": 4}
# the image cells and the input channels of their configuration
CELLS = {"cifar10-secagg-l8": 3, "mnist-secagg-l2": 1}


def overrides(reference, in_channels: int = 3) -> dict:
    model = dict(MODEL, in_channels=in_channels, num_classes=10)
    return {"config": {"model": MODEL,
                       "param_count": reference.param_count(model),
                       "dataset": {"shape": [16, 16, in_channels], "n_train": 1200, "n_test": 512},
                       "protocol": {"n_clients": 6, "clients_per_round": 3, "batch_size": 8,
                                    "max_eval_batches": 2}},
            "traffic": {"local_steps": 2}}
