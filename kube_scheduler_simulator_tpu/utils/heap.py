"""Keep full garbage collections off what a session holds for good.

A generation-2 collection walks every container object the process holds.
After an import those are the cluster's manifests, and after a session's
first pass also the rows the pass built of them (state/boundcarry.py): at
150,000 pods a full collection is most of a second, and CPython starts one
whenever the young generations have grown by a quarter of the old — in the
middle of a pass.  settle_heap() runs that collection once, where the wait
is part of loading anyway, and moves the survivors to the permanent
generation (gc.freeze()), which later collections do not walk.  Objects
frozen here are still freed by reference counting when the store drops
them; only a cycle among them would stay, and manifests hold none.
"""

from __future__ import annotations

import gc


def settle_heap() -> None:
    gc.collect()
    gc.freeze()
