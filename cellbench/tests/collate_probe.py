"""What the ResNet cell's collate costs on this machine, with and
without ``reuse_large_host_buffers``: ``numpy.stack`` of 128 float32
images of 224 px (77 MB) in a feeder thread, three batches alive at a
time as under ``prefetch_to_device(depth=2)``.  No JAX, no chip work:

    python3 -m cellbench.tests.collate_probe [reuse]

prints the per-batch times in ms.  Run in a young machine before
anything else has touched its memory (``PERF.md`` section 6, PR 26)."""

import collections
import statistics
import sys
import threading
import time

import numpy as np


def main(argv) -> int:
    if "reuse" in argv:
        from cellbench.runners.common import reuse_large_host_buffers

        reuse_large_host_buffers()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((512, 224, 224, 3), dtype=np.float32)
    times, alive = [], collections.deque(maxlen=3)

    def feeder():
        for _ in range(120):
            idx = rng.permutation(512)[:128]
            t = time.perf_counter()
            alive.append(np.stack([x[j] for j in idx]))
            times.append((time.perf_counter() - t) * 1e3)

    thread = threading.Thread(target=feeder)
    thread.start()
    thread.join()
    q = statistics.quantiles(times, n=10)
    print("reuse" if "reuse" in argv else "default",
          f"first {times[0]:.1f} median {statistics.median(times):.1f} "
          f"p10 {q[0]:.1f} p90 {q[-1]:.1f} last {times[-1]:.1f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
