"""Put the checkout root (for `vbench`) and `src` (for the program) first on
the import path of the benchmark's tests."""

import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if p in sys.path:
        sys.path.remove(p)
    sys.path.insert(0, p)
