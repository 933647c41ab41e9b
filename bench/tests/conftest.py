import os
import sys

# CPU rehearsal: these tests drive the benchmark's pieces without a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
