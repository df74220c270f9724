import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import tempfile  # noqa: E402

from bench import run as RUN  # noqa: E402

# the forward programs the tests' runs compile are kept out of the checkout
RUN.PROGRAMS_DIR = Path(tempfile.mkdtemp(prefix="bench_programs_"))
