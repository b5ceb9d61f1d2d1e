"""The benchmark's own tests run on the CPU at a rehearsal size:

    python -m pytest chipbench/tests -q

The environment is set before JAX is imported: the CPU backend and the
eight virtual devices tests/conftest.py asks for too (the mesh rehearsal
takes four of them), so that one pytest process can hold both."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):       # as tests/conftest.py guards its own
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
