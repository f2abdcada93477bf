"""``benchmark/controls.py`` with each breakage named by its function
in ``benchmark/lib/faults.py``, for cells whose generator the fault
tables there do not list:

    python3 benchmark/controls_named.py --workload crush10k_lrc.remap_lrc8 \
        --seconds 20 --seeds 11,12 --modes program placement_stale_epoch

Mode ``program`` patches nothing; any other mode is a function of
``lib/faults.py``, held open around the window.  Needs a TPU, like the
benchmark; the benchmark's own runs never run this.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark import controls  # noqa: E402
from benchmark.lib import faults  # noqa: E402


def patch_for(_generator: str, mode: str):
    return None if mode == "program" else getattr(faults, mode)


controls.patch_for = patch_for

if __name__ == "__main__":
    sys.exit(controls.main())
