"""Where the weights can live: the directory survey of ``chip_smoke.py``.

The driver's machine once refused a 3.26 GiB ``params.bin`` with EFBIG in a
directory that had taken the same file before (PERF.md, PR 21): a size limit
belongs to the directory and to whoever started the machine, and no
``getrlimit`` of this process shows it. So each directory the benchmark may
write to is asked, with a sparse file, how long a file it accepts, and the
files go where they fit. The depth of a configuration is NEVER changed at run
time: if no directory takes the file the run fails and names the limit found.

The benchmark may write inside its checkout and under ``TMPDIR``, ``HOME``
and ``XDG_CACHE_HOME`` (the driver gives each side its own), nowhere else.
"""

from __future__ import annotations

import errno
import os
import shutil
import tempfile

# errnos with which a directory says "not here" rather than "you are wrong"
NO_ROOM = (errno.EFBIG, errno.ENOSPC, errno.EDQUOT)


class NoRoom(Exception):
    """No directory takes the files a configuration needs."""


def max_file_bytes(directory: str, want: int) -> int:
    """The longest file, up to ``want`` bytes, that ``directory`` accepts.
    A limit refuses the offset, not the data, so one byte written at the end
    of a sparse file meets it without filling the disk."""
    fd, path = tempfile.mkstemp(prefix="bench-probe-", dir=directory)
    try:
        def accepts(size: int) -> bool:
            try:
                os.ftruncate(fd, size)
                os.pwrite(fd, b"\0", size - 1)
            except OSError as e:
                if e.errno not in NO_ROOM:
                    raise
                return False
            finally:
                os.ftruncate(fd, 0)
            return True

        if accepts(want):
            return want
        lo, hi = 0, want            # accepts(lo), not accepts(hi)
        while hi - lo > max(1, want >> 12):
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if accepts(mid) else (lo, mid)
        return lo
    finally:
        os.close(fd)
        os.unlink(path)


def ram_backed(directory: str) -> bool:
    """Whether ``directory`` is on a tmpfs: files there take the memory the
    server's host tier needs too."""
    best, fstype = "", ""
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mount, kind = line.split()[:3]
                if (len(mount) > len(best)
                        and os.path.commonpath((directory, mount)) == mount):
                    best, fstype = mount, kind
    except OSError:
        return False
    return fstype in ("tmpfs", "ramfs")


def candidates(checkout: str) -> list[str]:
    """The directories the contract lets the benchmark write to."""
    places = [tempfile.gettempdir(), os.path.join(checkout, "benchmark"),
              os.environ.get("XDG_CACHE_HOME", ""), os.environ.get("HOME", "")]
    seen: dict[str, None] = {}
    for p in places:
        if p and os.path.isdir(p) and os.access(p, os.W_OK | os.X_OK):
            seen.setdefault(os.path.realpath(p))
    return list(seen)


def survey(checkout: str, want_file: int) -> list[dict]:
    """One entry per candidate directory, disks before memory and then by
    free space: path, free bytes, the longest file it accepts."""
    rooms = []
    for path in candidates(checkout):
        rooms.append({"path": path, "ram": ram_backed(path),
                      "free": shutil.disk_usage(path).free,
                      "max_file": max_file_bytes(path, want_file)})
    return sorted(rooms, key=lambda r: (r["ram"], -r["free"]))


def choose(checkout: str, file_bytes: int, total_bytes: int,
           say=print) -> str:
    """The directory that takes a file of ``file_bytes`` and ``total_bytes``
    in all; prints every directory's limits. Raises ``NoRoom`` otherwise."""
    rooms = survey(checkout, file_bytes)
    for r in rooms:
        say(f"survey: {r['path']}: {'memory' if r['ram'] else 'disk'}, free "
            f"{r['free']} bytes, longest file "
            f"{'>= ' if r['max_file'] == file_bytes else ''}{r['max_file']} bytes")
    for r in rooms:
        if r["max_file"] >= file_bytes and r["free"] >= 1.05 * total_bytes:
            return r["path"]
    raise NoRoom(
        f"no directory takes a file of {file_bytes} bytes and {total_bytes} "
        f"bytes in all: {rooms}. The artifact format is one params.bin; lower "
        "the depth IN THE CONFIGURATION FILE (and in `reduced`), not here.")
