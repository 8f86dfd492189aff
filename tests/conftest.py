import os
import sys

# Tests must see exactly 1 device (the dry-run sets its own 512-device flag in
# its own process); never set XLA_FLAGS globally here.
# Tests keep JAX's persistent compile cache off, in the entry points they run
# as subprocesses too (those call repro.launch.runtime.enable_compile_cache).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
