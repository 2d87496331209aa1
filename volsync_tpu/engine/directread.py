"""The backup's reader of a file that fits one segment: one descriptor.

``TreeBackup`` reads such a file where it hashes it, through one
``os.open``: no ``pathlib``, no buffered ``io`` object, no read-ahead
reader and no thread (there is no segment N+1 whose read could overlap
the device's work on segment N). A system call costs 0.13-0.16 ms on
the chip's host and the Python beside it next to nothing (PERF.md
section 6, PR 38), so what a file costs here is its calls: ``open``,
one ``read``, ``fstat``, ``close``.

The ``fstat`` is the entry's stamp. It is taken after the last read,
through the descriptor the bytes came through, so it describes the
inode that was read whatever was renamed over its name or unlinked
since. It also stands in for the read that would find nothing: a read
that came back short of what it was asked has seen the end of a regular
file, and a size that is no more than what was read says so for
certain. A file that grew after the walk is read on to its end.
"""

from __future__ import annotations

import os

_PIECE = 1024 * 1024  # a further read of a file that outgrew the walk


class DirectReader:
    """``read`` / ``readinto`` / ``close`` and a context manager over
    one unbuffered descriptor: what ``stream_chunk_batches`` and
    ``service/hasher.py`` ``hash_file`` ask of a reader. ``stat`` is the
    ``fstat`` after the last read, there once the end was seen or the
    reader closed."""

    __slots__ = ("stat", "_fd", "_pos", "_short", "_eof")

    def __init__(self, path):
        self.stat = None
        self._pos = 0
        self._short = self._eof = False
        self._fd = os.open(path, os.O_RDONLY | os.O_CLOEXEC)

    def _at_end(self) -> bool:
        """Asked before a read. After a short read the ``fstat`` the
        entry needs anyway answers in place of a read of nothing."""
        if self._short:
            self._short = False
            st = os.fstat(self._fd)
            if st.st_size <= self._pos:
                self.stat, self._eof = st, True
        return self._eof

    def _took(self, got: int, asked: int) -> None:
        self._pos += got
        if got == 0:
            self._eof = True
        elif got < asked:
            self._short = True

    def read(self, n: int) -> bytes:
        if n <= 0 or self._at_end():
            return b""
        data = os.read(self._fd, n)
        self._took(len(data), n)
        return data

    def readinto(self, view) -> int:
        asked = memoryview(view).nbytes
        if not asked or self._at_end():
            return 0
        got = os.readv(self._fd, [view])
        self._took(got, asked)
        return got

    def close(self) -> None:
        fd, self._fd = self._fd, None
        if fd is None:
            return
        try:
            if self.stat is None:
                self.stat = os.fstat(fd)
        finally:
            os.close(fd)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def read_small(path, size: int) -> tuple[bytes, os.stat_result]:
    """(the file's bytes, the ``fstat`` after the last read). ``size``
    is what the walk saw: the first read asks for one byte more, so a
    file that did not grow is one ``read``, four calls in all."""
    with DirectReader(path) as reader:
        data = reader.read(size + 1)
        piece = reader.read(_PIECE)
        if piece:  # grew since the walk: on to its end, as read_bytes
            pieces = [data]
            while piece:
                pieces.append(piece)
                piece = reader.read(_PIECE)
            data = b"".join(pieces)  # lint: ignore[VL106] a file that outgrew the walk's size: rare, joined once
    return data, reader.stat
