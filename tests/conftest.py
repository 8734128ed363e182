import os
import sys

# NOTE: no XLA_FLAGS here on purpose — unit/smoke tests must see 1 real CPU
# device. Distribution tests spawn subprocesses that set
# --xla_force_host_platform_device_count themselves.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the benchmark's own modules (perfbench/), for the model-region checks
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))
