"""Share of the traced slice in which the device idled while the step loop
was planning, enqueueing or unpacking: the device's idle gaps (20 us and
more, as ``xplane.py`` finds them) cut by the ``loop.plan``,
``loop.dispatch`` and ``loop.process`` annotations the program writes into
the same profile (``hostspans.py``), averaged over workers. Host and
device events share the profiler's clock, so no wall clock joins them.

The whole table - idle seconds under every phase, ``loop.idle`` (waiting
for a request), ``loop.fetch``, ``loop.blocked``, the slice's edges and no
annotation at all, and every gap of a millisecond and more split by what
was open - is left in the run directory as ``loop_phases.worker<i>.json``. Nothing where the profile has no
``loop.*`` annotation (an older program)."""

import json
import os
import subprocess
import sys

import hostspans


def compute(run):
    shares = []
    for i, trace in enumerate(run.device_traces):
        out = subprocess.run(
            [sys.executable, hostspans.__file__, trace["mark"]["dir"]],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
        if out.returncode != 0:
            continue
        red = json.loads(out.stdout.strip().splitlines()[-1])
        with open(os.path.join(run.run_dir,
                               f"loop_phases.worker{i}.json"), "w") as f:
            json.dump(red, f)
        share = hostspans.host_share(red)
        if share is not None:
            shares.append(share)
    return sum(shares) / len(shares) if shares else None
