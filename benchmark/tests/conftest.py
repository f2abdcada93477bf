"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
