"""The benchmark's own cluster generator, estimator and quota division.

Copied from the program (``koordinator_tpu/harness/generators.py``
``quota_colocation``, ``_random_nodes`` and ``_random_pods``; the
estimator of ``model/snapshot.py``; the fair division of
``constraints/quota.py``) so that no later PR can move the yardstick by
editing the program.  It draws from the seed in the same order as the
originals, so at seed 0 the ``colo2k`` configuration is BASELINE.json
config 4 exactly as ``quota_colocation`` builds it
(``benchmark/tests/test_cluster.py``).  Nothing here imports the program.

Everything is dense numpy on the program's resource axis: int64 rows of
``R`` resources, cpu in milli-cores, byte resources in MiB.

Coscheduling gangs.  A configuration may declare
``"gangs": {"members": m, "min_member": k, "mode": "Strict"}``
(Koordinator's gang annotations ``gang.scheduling.koordinator.sh/
min-available`` and ``.../mode``).  Then every backlog row belongs to a
gang: gang ``g`` is rows ``[g*m, (g+1)*m)`` with id ``g``, the backlog is
a multiple of ``m``, and the gang table's min member is ``k`` for every
gang, for the whole run.  A gang's members are one job's identical
workers: one draw of (cpu, memory, BE coin, priority) per gang,
repeated over its rows, and one ElasticQuota tenant.  Only Strict gangs
that bind whole (``k == m``) are taken for now (``validate_gangs``).
"""

from __future__ import annotations

import math

import numpy as np

# The resource axis of the wire (model/resources.py RESOURCE_AXIS order).
R = 13
CPU, MEMORY, PODS = 0, 1, 3
BATCH_CPU, BATCH_MEMORY, MID_CPU, MID_MEMORY = 4, 5, 6, 7
MIB = 1024 * 1024

# Koordinator priority bands (apis/extension/priority.go) as the wire
# numbers them (model/snapshot.py PriorityClass).
CLASSES = {"koord-prod": 0, "koord-mid": 1, "koord-batch": 2,
           "koord-free": 3, "none": 4}
_TRANSLATE = {2: {CPU: BATCH_CPU, MEMORY: BATCH_MEMORY},
              1: {CPU: MID_CPU, MEMORY: MID_MEMORY}}


def _ceil_mib(nbytes: int) -> int:
    """Quantity.Value() of a byte count, on the MiB axis (rounded up)."""
    return -(-int(nbytes) // MIB)


class Nodes:
    """Node table: allocatable, requested and usage rows, names."""

    def __init__(self, alloc, requested, usage, names):
        self.alloc = alloc
        self.requested = requested
        self.usage = usage
        self.names = names


def make_nodes(rng, spec) -> Nodes:
    """``_random_nodes``: per node, cpu from the choices, memory per
    core, used cpu and memory as uniform fractions of allocatable."""
    n = int(spec["count"])
    alloc = np.zeros((n, R), np.int64)
    usage = np.zeros((n, R), np.int64)
    mem_per_core = int(spec["memory_mib_per_core"]) * MIB
    cpu_lo, cpu_hi = spec["usage_cpu_frac"]
    mem_lo, mem_hi = spec["usage_memory_frac"]
    for i in range(n):
        cpu = int(rng.choice(spec["cpu_milli"]))
        mem = (cpu // 1000) * mem_per_core
        used_frac = rng.uniform(cpu_lo, cpu_hi)
        alloc[i, CPU] = cpu
        alloc[i, MEMORY] = _ceil_mib(mem)
        alloc[i, PODS] = int(spec["pods"])
        usage[i, CPU] = int(cpu * used_frac)
        usage[i, MEMORY] = _ceil_mib(int(mem * rng.uniform(mem_lo, mem_hi)))
    names = [f"node-{i}" for i in range(n)]
    return Nodes(alloc, np.zeros((n, R), np.int64), usage, names)


class Pods:
    """Pod rows: requests, limits, priority, priority class, quota id,
    names."""

    def __init__(self, requests, limits, priority, pclass, quota, names):
        self.requests = requests
        self.limits = limits
        self.priority = priority
        self.pclass = pclass
        self.quota = quota
        self.names = names

    def __len__(self):
        return len(self.names)


def _pod_rows(spec, cpu, mem_mib, be, prio_draw):
    """Request, limit, priority and class rows from drawn columns."""
    n = len(cpu)
    req = np.zeros((n, R), np.int64)
    req[:, CPU] = cpu
    req[:, MEMORY] = mem_mib
    req[:, PODS] = 1
    lim = np.zeros((n, R), np.int64)
    factor = int(spec["limits_factor"])
    lim[:, CPU] = cpu * factor
    lim[:, MEMORY] = mem_mib * factor
    ls, bes = spec["ls"], spec["be"]
    prio = np.where(be, bes["priority"][0], ls["priority"][0]) + prio_draw
    pclass = np.where(be, CLASSES[bes["class"]], CLASSES[ls["class"]])
    return req, lim, prio.astype(np.int64), pclass.astype(np.int64)


def validate_gangs(config, traffic) -> None:
    """Raises ValueError where the configuration's ``gangs`` block is one
    this harness cannot run, or the traffic mix is not whole gangs."""
    g = config.get("gangs")
    if g is None:
        return
    backlog = int(traffic["backlog_pods"])
    m, k, mode = g.get("members"), g.get("min_member"), g.get("mode")
    if not isinstance(m, int) or m < 1:
        raise ValueError(f"gangs: members must be a whole number >= 1, "
                         f"not {m!r}")
    if mode != "Strict":
        raise ValueError(f"gangs: only mode 'Strict' is supported, not "
                         f"{mode!r} (NonStrict waits for a configuration "
                         f"that needs it)")
    if k != m:
        raise ValueError(f"gangs: only min_member == members is supported "
                         f"(a gang binds whole), not min_member {k!r} with "
                         f"members {m}")
    if traffic["rpc"] != "assign":
        raise ValueError(f"gangs: a gang is placed by Assign, not by "
                         f"{traffic['rpc']!r} traffic")
    if backlog % m:
        raise ValueError(f"gangs: backlog_pods {backlog} is not a multiple "
                         f"of members {m}")


def make_pods(rng, spec, count: int, prefix: str, members: int = 1) -> Pods:
    """``_random_pods``, drawn pod by pod in the original's order (cpu,
    memory, BE coin, priority), so seed 0 gives config 4's backlog.
    With ``members`` > 1 one draw is made per gang of that many rows."""
    n = count // members
    cpu = np.zeros(n, np.int64)
    mem = np.zeros(n, np.int64)
    be = np.zeros(n, bool)
    draw = np.zeros(n, np.int64)
    ls_spread, be_spread = spec["ls"]["priority"][1], spec["be"]["priority"][1]
    for i in range(n):
        cpu[i] = int(rng.choice(spec["cpu_milli"]))
        mem[i] = int(rng.choice(spec["memory_mib"]))
        be[i] = rng.uniform() < spec["be_share"]
        draw[i] = int(rng.randint(0, be_spread if be[i] else ls_spread))
    return _pods(spec, cpu, mem, be, draw, members, prefix, 0)


def draw_pods(gen, spec, count: int, prefix: str, first: int,
              members: int = 1) -> Pods:
    """Arrivals after set-up, drawn from the same distribution in bulk
    (``gen`` is a numpy Generator); names run on from ``first``.  With
    ``members`` > 1 one draw is made per gang of that many rows."""
    n = count // members
    cpu = gen.choice(np.asarray(spec["cpu_milli"], np.int64), n)
    mem = gen.choice(np.asarray(spec["memory_mib"], np.int64), n)
    be = gen.random(n) < spec["be_share"]
    spread = np.where(be, spec["be"]["priority"][1], spec["ls"]["priority"][1])
    draw = (gen.random(n) * spread).astype(np.int64)
    return _pods(spec, cpu, mem, be, draw, members, prefix, first)


def _pods(spec, cpu, mem, be, draw, members, prefix, first) -> Pods:
    """Pods from drawn columns, each draw repeated over ``members`` rows."""
    cpu, mem, be, draw = (np.repeat(c, members) for c in (cpu, mem, be, draw))
    count = len(cpu)
    req, lim, prio, pclass = _pod_rows(spec, cpu, mem, be, draw)
    names = [f"{prefix}-{first + i}" for i in range(count)]
    return Pods(req, lim, prio, pclass, np.full(count, -1, np.int64), names)


def estimate(requests, limits, pclass, scoring) -> np.ndarray:
    """defaultEstimator.EstimatePod (default_estimator.go:58-127) for
    every row: per weighted resource, the priority-translated slot of
    limits (scaled 100%) where limits exceed requests, else of requests
    scaled by its factor; a zero quantity takes the default request;
    the estimate never passes a positive limit."""
    n = requests.shape[0]
    out = np.zeros((n, R), np.int64)
    factors = {CPU: scoring["estimated_scaling_factors"]["cpu"],
               MEMORY: scoring["estimated_scaling_factors"]["memory"]}
    defaults = {CPU: scoring["default_milli_cpu_request"],
                MEMORY: scoring["default_memory_request_mib"]}
    for idx in (CPU, MEMORY):
        real = np.full(n, idx)
        for cls, table in _TRANSLATE.items():
            real = np.where(pclass == cls, table[idx], real)
        req = requests[np.arange(n), real]
        lim = limits[np.arange(n), real]
        use_lim = lim > req
        qty = np.where(use_lim, lim, req)
        fac = np.where(use_lim, 100, factors[idx])
        # Go math.Round(q * f / 100), half away from zero, in integers
        est = (qty * fac * 2 + 100) // 200
        est = np.where((lim > 0) & (est > lim), lim, est)
        out[:, idx] = np.where(qty == 0, defaults[idx], est)
    return out


class Quotas:
    """ElasticQuota groups: min, max, shared weight, declared dims."""

    def __init__(self, qmin, qmax, weight):
        self.min = qmin
        self.max = qmax
        self.weight = weight


def make_quotas(rng, spec, alloc) -> Quotas:
    """``quota_colocation``'s tenants: min at ``min_tenths``/10 of the
    cluster's cpu and memory split evenly, max at ``max_tenths``/10,
    a shared weight drawn per tenant."""
    t = int(spec["tenants"])
    total_cpu = int(alloc[:, CPU].sum())
    total_mem = int(alloc[:, MEMORY].sum()) * MIB
    qmin = np.zeros((t, R), np.int64)
    qmax = np.zeros((t, R), np.int64)
    weight = np.zeros(t, np.int64)
    for i in range(t):
        for arr, tenths in ((qmin, spec["min_tenths"]),
                            (qmax, spec["max_tenths"])):
            arr[i, CPU] = total_cpu * tenths // 10 // t
            arr[i, MEMORY] = _ceil_mib(total_mem * tenths // 10 // t)
        weight[i] = int(rng.randint(*spec["shared_weight"]))
    return Quotas(qmin, qmax, weight)


def quota_runtime(quotas: Quotas, request, total):
    """RuntimeQuotaCalculator (runtime_quota_calculator.go:109-155):
    per resource, groups asking more than their min start at min and
    share what is left by weight, iterating while some stay short;
    groups under min keep their request (they lend the rest).  Returns
    (runtime [Q, R], limited [Q, R]): declared dims, and dims with a
    runtime, are limited; the others fall open."""
    q = quotas.min.shape[0]
    runtime = np.zeros((q, R), np.int64)
    declared = (quotas.min != 0) | (quotas.max != 0)
    for r in range(R):
        if total[r] == 0 and not request[:, r].any():
            continue
        rt = [0] * q
        to_partition = int(total[r])
        shares = 0
        short = []
        for i in range(q):
            gmin = int(quotas.min[i, r])
            want = min(int(request[i, r]), int(quotas.max[i, r]))
            if want > gmin:
                short.append(i)
                shares += int(quotas.weight[i])
                rt[i] = gmin
            else:
                rt[i] = want
            to_partition -= rt[i]
        while to_partition > 0 and shares > 0 and short:
            still, next_shares, surplus = [], 0, 0
            for i in short:
                w = int(quotas.weight[i])
                rt[i] += int(math.floor(w * to_partition / shares + 0.5))
                want = min(int(request[i, r]), int(quotas.max[i, r]))
                if rt[i] < want:
                    still.append(i)
                    next_shares += w
                else:
                    surplus += rt[i] - want
                    rt[i] = want
            to_partition, shares, short = surplus, next_shares, still
        for i in range(q):
            runtime[i, r] = min(rt[i], int(quotas.max[i, r]))
    limited = declared | (runtime != 0)
    return np.where(limited, runtime, 0), limited.astype(np.int64)
