import os
import sys

# Tests must see the real single-device CPU environment — the 512-device
# override belongs ONLY to repro.launch.dryrun (assignment requirement).
assert "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""), (
    "do not set XLA_FLAGS globally; dryrun.py owns the 512-device override"
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
