import os
import sys

# The harness is imported as `harness`, the program as `stateright_tpu`.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_BENCH, os.path.dirname(_BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
