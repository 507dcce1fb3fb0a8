"""Share of the traced slice in which the device idled while ready work
waited for a thread or for the event loop: the device's idle gaps inside a
``loop.dispatch`` or ``loop.fetch`` annotation of the loop's thread but
outside its twin of the same ``seq`` on the worker thread - the hand-over
before the call and the resume after it (``dispatchspans.py``), averaged
over workers. It is the part of the device's idle time that the frames the
same event loop serialises cost. Nothing where the profile has no
``dispatch.*`` annotation (an older program)."""

import dispatchspans


def compute(run):
    return dispatchspans.share(run, "handover")
