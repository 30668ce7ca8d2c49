"""The benchmark's own tests run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are not part of the repository's test suite (``testpaths`` there is
``tests``).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "metrics"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
