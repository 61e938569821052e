"""Puts the benchmark's and the program's sources on ``sys.path``."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"name": "wm-tiny", "lat": 32, "lon": 64, "channels": 8, "patch": 4,
        "d_emb": 32, "d_tok": 64, "d_ch": 32, "n_layers": 2,
        "precision": "fp32", "kernel": "xla", "remat": False,
        "source": "test size"}
