"""Share of the traced slice in which the device idled while a dispatch's
call was putting its arguments on the device and calling the jitted
program: the device's idle gaps cut by the ``dispatch.upload`` and
``dispatch.enqueue`` annotations of the same profile (``dispatchspans.py``;
the table in ``dispatch_phases.worker<i>.json`` keeps the two apart),
averaged over workers. It is the part of ``loop.idle_behind_host_share``
that one packed upload for an unchained block would take. Nothing where the
profile has no ``dispatch.*`` annotation (an older program)."""

import dispatchspans


def compute(run):
    return dispatchspans.share(run, "enqueue")
