"""Puts the checkout's root on the path, so the benchmark's own package
(``bench/``) imports as it does under ``python3 bench/run.py``."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
