import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(BENCH_DIR, "metrics"), BENCH_DIR,
          os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)
