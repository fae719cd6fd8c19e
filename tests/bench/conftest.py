"""The CPU size of the ``train`` traffic kind, beside the ``sim`` kinds'
in ``bench_tiny.TINY_TRAFFIC``: ``tiny_root`` shrinks every traffic
file of the benchmark, so each kind needs an entry there."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench_tiny_train import TINY_TRAIN_TRAFFIC  # noqa: E402

bench_tiny.TINY_TRAFFIC.setdefault("train:device", TINY_TRAIN_TRAFFIC)
