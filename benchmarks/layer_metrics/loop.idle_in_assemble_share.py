"""Share of the traced slice in which the device idled while a dispatch's
call was turning its plan into host arrays: the device's idle gaps (20 us
and more, as ``xplane.py`` finds them) cut by the ``dispatch.assemble``
annotations the engine writes into the same profile around the numpy loops
of a step's arguments (``dispatchspans.py``), averaged over workers. It is
the part of ``loop.idle_behind_host_share`` that planning and assembling
step *n + 1* while step *n* runs would take.

The whole table - idle seconds under ``handover``, ``assemble``, ``upload``,
``enqueue``, ``wait``, ``resume`` and ``other`` of the ``loop.dispatch`` and
the ``loop.fetch`` phases, once in all and once per dispatch kind - is left
in the run directory as ``dispatch_phases.worker<i>.json``. Nothing where
the profile has no ``dispatch.*`` annotation (an older program)."""

import dispatchspans


def compute(run):
    return dispatchspans.share(run, "assemble")
