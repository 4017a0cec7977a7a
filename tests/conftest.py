"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Both variables are set before anything imports jax.  Tests prove
decisions and counts on the CPU; sharding correctness is validated on 8
virtual CPU devices exactly as ``__graft_entry__.dryrun_multichip``
does.  Nothing here touches an accelerator: the chip is exercised by
``chip_smoke.py`` and ``bench.py``.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
