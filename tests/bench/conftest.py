"""The benchmark's tests import it as the package ``bench`` from the
repository's root, as ``bench/run.py`` does."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
