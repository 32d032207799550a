"""Plain float32 reference of Zamba2's forward pass, for the equivalence tests
(``tests/test_zamba2.py``).

The reference is kept once, with the benchmark, as
``chipbench/reference/zamba2.py`` (it imports nothing of ``repro``); this
module loads that file, so the tests exercise exactly the code the chip's
check runs.
"""

import importlib.util
from pathlib import Path

_PATH = Path(__file__).resolve().parents[1] / "chipbench" / "reference" / "zamba2.py"
_spec = importlib.util.spec_from_file_location("chipbench_reference_zamba2", _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

Drawn = _mod.Drawn
draw_weights = _mod.draw_weights
forward = _mod.forward
hybrid_layers = _mod.hybrid_layers
