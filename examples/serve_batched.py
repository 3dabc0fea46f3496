"""Batched serving example: continuous batching with slot retirement
(the serving-level dead-block prediction).

Run:  PYTHONPATH=src python examples/serve_batched.py
"""

import sys

from repro.launch.serve import main

sys.argv = [sys.argv[0], "--arch", "gemma-7b", "--reduce", "--requests",
            "6", "--max-new", "8", "--max-batch", "3", "--max-seq", "96"]
main()
