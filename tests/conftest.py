"""Suite-wide setup: make ``src/`` importable before any test module."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))
