import os
import sys

# src/ layout without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Fall back to the vendored deterministic hypothesis stub when the real
# package is unavailable (see tests/_stubs/hypothesis/__init__.py).
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    sys.path.append(os.path.join(os.path.dirname(__file__), "_stubs"))
