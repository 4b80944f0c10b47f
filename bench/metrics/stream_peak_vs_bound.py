"""The device allocator's peak bytes in use, on the fullest chip, as a
share of the bytes a streamed plan promises to stay within, in percent.
The promise is the plan's own two public numbers: ``resident_device_bytes``
(resident arrays plus two worst-case staged waves and their workspace)
and ``schedule_stats["streaming"]["resident_bytes"]`` (the resident set
with the state and its accumulator), so the vertex-level arrays count
twice in it.  Above 100 the plan holds more than it says it does.
Nothing to read for an in-core plan or where the runtime has no peak."""


def read(run):
    if not run.stream_bound_bytes or not run.peak_bytes:
        return None
    return 100.0 * run.peak_bytes / run.stream_bound_bytes
