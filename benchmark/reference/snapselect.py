"""Which snapshot a restore takes, by the rule of
``mover-restic/entry.sh:146-200`` (``select_restic_snapshot_to_restore``)
in plain Python on a list of times: the snapshots in order of time, of
those at or before ``restore_as_of`` (all of them where none is given)
the newest, and ``previous`` more back from it.
"""

from __future__ import annotations

from datetime import datetime, timezone
from typing import Optional


def _utc(t: datetime) -> datetime:
    return t.replace(tzinfo=timezone.utc) if t.tzinfo is None else t


def select(times: list[datetime], restore_as_of: Optional[datetime] = None,
           previous: int = 0) -> Optional[int]:
    """The index into ``times`` of the snapshot to restore, or None
    where no snapshot matches."""
    order = sorted(range(len(times)), key=lambda i: _utc(times[i]))
    if restore_as_of is not None:
        order = [i for i in order
                 if _utc(times[i]) <= _utc(restore_as_of)]
    if previous < 0 or previous >= len(order):
        return None
    return order[len(order) - 1 - previous]
