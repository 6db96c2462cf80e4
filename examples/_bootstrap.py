"""Shared example bootstrap: make the repo importable when run from
anywhere."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
