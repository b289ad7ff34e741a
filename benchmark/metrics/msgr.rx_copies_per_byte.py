"""Bytes the receive path moved between user-space buffers for every
byte it received: over the window's sections, the sum of ``copied`` of
``msgr.recv`` (bytes moved on the way to a whole frame: a small frame
cut out of the connection's reusable buffer, what had arrived of a
large frame moved into the frame's own buffer) and of ``msgr.decode``
(bytes the message's decode copied out of the frame) over the sum of
``bytes`` of ``msgr.recv``.  A frame received into a buffer of its own
and decoded as views of it reads near 0; a receive path that joins
chunks, cuts the payload out and slices every field reads 3 to 4.  A
program whose sections carry no ``copied`` gives nothing to read."""
SOURCE = "program_span"
LAYER = "messenger"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    _, red = spans.for_ctx(ctx)
    names = (red or {}).get("names", {})
    recv = names.get("msgr.recv", {}).get("sums", {})
    decode = names.get("msgr.decode", {}).get("sums", {})
    if "copied" not in recv and "copied" not in decode:
        return None
    if recv.get("bytes", 0) <= 0:
        return None
    return (recv.get("copied", 0) + decode.get("copied", 0)) / recv["bytes"]
