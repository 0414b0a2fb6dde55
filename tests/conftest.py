import os
import sys

# tests run on the CPU: a test process must never take the chip, which
# belongs to one process at a time
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see one device; only launch/dryrun.py uses 512.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
