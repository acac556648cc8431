"""Run one benchmark cell once; see ``harness/main.py``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
