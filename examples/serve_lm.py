"""Batched **language-model** serving example (LM-era infrastructure kept
from this repo's shared training stack): prefill a prompt batch, then decode
with the ring KV cache — the path the decode_32k / long_500k dry-run cells
validate at 256/512 chips.

For serving the SLAM engine itself — many concurrent RGB-D streams through
one stacked-session dispatch — see ``examples/serve_slam.py`` (SessionPool /
``step_many``), which is this pattern applied to the RTGS pipeline.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch zamba2-1.2b --gen 24
"""

import sys

from repro.launch import serve

if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    sys.argv = [sys.argv[0]] + (sys.argv[1:] or ["--arch", "zamba2-1.2b", "--gen", "24"])
    serve.main()
