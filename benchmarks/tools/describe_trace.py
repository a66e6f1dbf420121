"""What a profiler trace holds: planes, lines, event counts and the
first events of each line.  `python3 benchmarks/tools/describe_trace.py
<file.xplane.pb> [events per line]`"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import trace_reduce  # noqa: E402

if __name__ == "__main__":
    print(trace_reduce.describe(sys.argv[1],
                                int(sys.argv[2]) if len(sys.argv) > 2
                                else 6))
