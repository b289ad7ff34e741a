"""What the host's threads were doing, from the program's own
always-on stack sampler (utils/sampler.py, 67 Hz, every thread's
Python stack).  Read before and after the window and differenced.

The profiler's trace carries no span of the program's threads (it has
no TraceAnnotation), so this is the only view of the host inside an
idle gap that needs no change to the program.  A sample says which
function a thread's Python stack ended in, not who held the
interpreter lock: threads parked in a wait are left out, and what
remains is who was running or wanted to.
"""
import re

WAIT_LEAVES = frozenset((
    "Condition.wait", "Event.wait", "EpollSelector.select", "_read_exact",
    "socket.accept", "Queue.get", "Thread.join", "create_connection",
    "Semaphore.acquire", "StackSampler._run", "sleep",
))


def snapshot() -> dict:
    from ceph_tpu.utils.sampler import global_sampler
    out = {}
    for line in global_sampler().dump_folded():
        key, _, count = line.rpartition(" ")
        out[key] = int(count)
    return out


def busy_shares(before: dict, after: dict) -> list:
    """[(\"<thread group>;<leaf function>\", share of non-waiting
    samples)], largest first."""
    agg = {}
    for key, count in after.items():
        n = count - before.get(key, 0)
        if n <= 0:
            continue
        parts = key.split(";")
        leaf = parts[-1]
        if leaf in WAIT_LEAVES or parts[0].startswith("bench-caller"):
            continue
        group = re.sub(r"\d+", "N", parts[0])
        label = f"{group};{leaf}"
        agg[label] = agg.get(label, 0) + n
    total = sum(agg.values())
    if not total:
        return []
    return sorted(((k, v / total) for k, v in agg.items()),
                  key=lambda kv: -kv[1])


def split_unspanned(idle_gaps: list, shares: list) -> list:
    """Idle seconds that no host span of the trace explains (only a
    caller waiting, or nothing) are split by what the program's stack
    sampler saw the host's threads doing in the window."""
    blind = ("client.wait (no other host span)", "unattributed")
    rest = [[n, s] for n, s in idle_gaps if n not in blind]
    dark = sum(s for n, s in idle_gaps if n in blind)
    if not shares or dark <= 0:
        return idle_gaps
    rest += [[f"sampled:{label}", dark * share] for label, share in shares]
    return sorted(rest, key=lambda kv: -kv[1])[:10]
