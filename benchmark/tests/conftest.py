"""The yardstick's own tests run on the CPU, whatever the machine has: set
before jax is imported by anything. No TPU library call is made while a
module is imported, in a skipif or in a parametrize."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
