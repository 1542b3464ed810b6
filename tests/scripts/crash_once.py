"""Restart-policy check script: rank 1 dies on the FIRST group attempt
(leaving a marker), every rank completes on the restart — driven by
tests/test_cli.py::test_max_restarts_recovers_crashed_group."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import accelerate_tpu as atx
from accelerate_tpu.state import ProcessState

marker = sys.argv[1]
ps = ProcessState()
if ps.process_index == 1 and not os.path.exists(marker):
    try:
        with open(marker, "w") as f:
            f.write("crashed")
    except OSError:
        # An uncreatable marker means "crash every time" — and crash HARD
        # either way: an exception would leave through the interpreter's
        # exit hooks, where the distributed shutdown barrier waits half a
        # minute for the peer this rank just abandoned.
        pass
    print(f"[proc {ps.process_index}] CRASHING ONCE", flush=True)
    os._exit(17)

# Survived (restart for everyone): do real collective work so the restarted
# rendezvous is proven functional, not just alive.
from accelerate_tpu.ops import collectives

vals = collectives.gather_object([ps.process_index])
assert sorted(vals) == list(range(ps.num_processes)), vals
ps.wait_for_everyone()
print(f"[proc {ps.process_index}] RESTART OK", flush=True)
