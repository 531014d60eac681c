import sys
from pathlib import Path

# the benchmark package lives at the repository's root
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
