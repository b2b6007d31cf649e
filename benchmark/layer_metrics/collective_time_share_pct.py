"""Share of the devices' busy time spent in collective instructions
(``all-reduce``, ``all-gather``, ``all-to-all``, ``reduce-scatter``,
``collective-permute`` and their ``-start`` / ``-done`` forms), by instruction
name from the device trace, averaged over the devices like ``busy_s``. A
collective ends when its slowest chip arrives, so the share holds the waiting
for that chip too, which is the point. One chip reads 0."""
import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)"
    r"(-start|-done)?$")


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = sum(s for name, s in trace["device_ops"] if COLLECTIVE.match(name))
    return 100.0 * seconds / trace["busy_s"]
