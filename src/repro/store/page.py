"""The form of a stored page, in one place.

A page store keeps each page as the payload it was given: ``bytes``, or,
where the writer handed its buffer over (``BlobClient.write_many``'s
``given``), a read-only numpy row of that buffer, which is no copy and
no object the interpreter's cycle collector tracks.  Code that reads a
payload's bytes goes through :func:`page_view`, so no other module needs
to know which of the two it holds.
"""

from __future__ import annotations

from typing import Union


def page_view(payload) -> Union[bytes, memoryview]:
    """A stored payload as bytes that slice, iterate and compare by
    content, without a copy: a ``bytes`` page itself, else a
    ``memoryview`` of the kept row."""
    return payload if type(payload) is bytes else memoryview(payload)
