"""README's advertised test count must match what pytest collects.

Round 3's README said 457 while the suite collected 467 (hand-maintained
count drifted within the round).  The cure: make the committed number
a checked function of the tree.  Update the count in README.md's
"Tests (`N`: ..." line whenever this fails.
"""

import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_readme_test_count_matches_collected():
    with open(os.path.join(REPO, "README.md")) as f:
        m = re.search(r"Tests \(`(\d+)`", f.read())
    assert m, "README.md lost its Tests (`N`: ...) line"
    claimed = int(m.group(1))

    # independent full-suite collection so this passes/fails identically
    # under filtered runs (-k, single file) and the full suite
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/", "-q", "--collect-only",
         "-p", "no:cacheprovider"],
        capture_output=True, text=True, cwd=REPO,
    )
    m2 = re.search(r"(\d+) tests collected", r.stdout)
    assert m2, f"could not parse collection output:\n{r.stdout[-2000:]}"
    collected = int(m2.group(1))
    assert claimed == collected, (
        f"README.md claims {claimed} tests but the suite collects "
        f"{collected}; update the README line"
    )
