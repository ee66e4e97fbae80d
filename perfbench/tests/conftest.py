import sys
from pathlib import Path

# the checkout's root, so that ``perfbench`` imports as a package
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
