"""The host-counter sampler (``narwhal_tpu/utils/hoststat.py``) on
hand-made ``/proc`` and ``/sys/fs/cgroup`` trees: cgroup v2 and v1, PSI
from the cgroup or from ``/proc/pressure``, steal from ``/proc/stat``; a
missing or unreadable file leaves its keys out and never reads 0."""

import os

import pytest

from narwhal_tpu.utils import hoststat
from narwhal_tpu.utils.hoststat import HostStat

CLK_TCK = os.sysconf("SC_CLK_TCK")

# user nice system idle iowait irq softirq steal guest guest_nice
PROC_STAT = (
    f"cpu  100 0 50 9000 10 0 5 {4 * CLK_TCK} 0 0\n"
    "cpu0 50 0 25 4500 5 0 2 100 0 0\n"
    "cpu1 50 0 25 4500 5 0 3 100 0 0\n"
    "cpu2 0 0 0 0 0 0 0 0 0 0\n"
    "cpu3 0 0 0 0 0 0 0 0 0 0\n"
    "intr 12345 0 0\nctxt 999\nbtime 1700000000\n"
)
PSI_FULL = (
    "some avg10=0.00 avg60=0.00 avg300=0.00 total={some}\n"
    "full avg10=0.00 avg60=0.00 avg300=0.00 total={full}\n"
)


def put(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


@pytest.fixture
def v2(tmp_path):
    """A v2 host: the process in /app.slice/node.scope, PSI there."""
    proc, root = tmp_path / "proc", tmp_path / "cgroup"
    put(proc / "self" / "cgroup", "0::/app.slice/node.scope\n")
    put(root / "cgroup.controllers", "cpu io memory pids\n")
    cg = root / "app.slice" / "node.scope"
    put(cg / "cpu.stat", (
        "usage_usec 7000000\nuser_usec 5000000\nsystem_usec 2000000\n"
        "nr_periods 120\nnr_throttled 9\nthrottled_usec 1500000\n"
    ))
    put(cg / "memory.pressure", PSI_FULL.format(some=300000, full=200000))
    put(cg / "io.pressure", PSI_FULL.format(some=50000, full=40000))
    put(cg / "cpu.pressure", PSI_FULL.format(some=900000, full=0))
    put(proc / "stat", PROC_STAT)
    return proc, root


@pytest.fixture
def v1(tmp_path):
    """A v1 host: cpu,cpuacct under /docker/abc, PSI in /proc/pressure."""
    proc, root = tmp_path / "proc", tmp_path / "cgroup"
    put(proc / "self" / "cgroup", (
        "12:memory:/docker/abc\n4:cpu,cpuacct:/docker/abc\n0::/\n"
    ))
    cg = root / "cpu,cpuacct" / "docker" / "abc"
    put(cg / "cpu.stat", (
        "nr_periods 300\nnr_throttled 4\nthrottled_time 2500000000\n"
    ))
    put(proc / "pressure" / "memory", PSI_FULL.format(some=10, full=5))
    put(proc / "pressure" / "io", PSI_FULL.format(some=0, full=0))
    put(proc / "pressure" / "cpu", "some avg10=0.00 total=2000000\n")
    put(proc / "stat", PROC_STAT)
    return proc, root


def test_cgroup_v2_counters_are_read_in_seconds(v2):
    proc, root = v2
    host = HostStat(str(proc), str(root))
    s = host.sample()
    assert s == pytest.approx({
        "cpu_throttled": 1.5, "cpu_nr_throttled": 9,
        "memory_some": 0.3, "memory_full": 0.2, "io_full": 0.04,
        "cpu_some": 0.9,
        # steal over the 4 online CPUs: 4 s of ticks / 4
        "cpu_steal": 1.0,
    })
    assert set(host.keys) == set(s)


def test_cgroup_v1_throttled_time_is_in_nanoseconds(v1):
    proc, root = v1
    s = HostStat(str(proc), str(root)).sample()
    assert s["cpu_throttled"] == pytest.approx(2.5)
    assert s["cpu_nr_throttled"] == 4
    # No v2 mount: PSI from /proc/pressure, only the lines kept.
    assert s["memory_some"] == pytest.approx(1e-5)
    assert s["memory_full"] == pytest.approx(5e-6)
    assert s["io_full"] == 0.0 and "io_some" not in s
    assert s["cpu_some"] == pytest.approx(2.0) and "cpu_full" not in s


def test_a_cgroup_path_not_under_the_mount_is_the_mount_itself(tmp_path):
    """A container sees its own cgroup at the mount's root while
    ``/proc/self/cgroup`` names the host's path (the v5e host does)."""
    proc, root = tmp_path / "proc", tmp_path / "cgroup"
    put(proc / "self" / "cgroup", "2:cpuacct:/pod-1\n1:cpu:/pod-1\n")
    put(root / "cpu" / "cpu.stat", "nr_throttled 3\nthrottled_time 7000\n")
    os.makedirs(root / "cpuacct")
    assert hoststat.cgroup_dirs(
        (proc / "self" / "cgroup").read_text(), str(root)
    ) == (None, str(root / "cpu"))
    s = HostStat(str(proc), str(root)).sample()
    assert s["cpu_throttled"] == pytest.approx(7e-6)
    assert s["cpu_nr_throttled"] == 3


def test_a_missing_file_leaves_its_keys_out_never_zero(tmp_path):
    """The v5e host's case: a cpu controller without cpu.stat, no
    /proc/pressure, and a /proc/stat whose every field reads 0
    (no accounting kept): no key at all."""
    proc, root = tmp_path / "proc", tmp_path / "cgroup"
    put(proc / "self" / "cgroup", "2:cpuacct:/x\n1:cpu:/x\n")
    put(root / "cpu" / "cpu.cfs_quota_us", "-1\n")
    put(root / "cpuacct" / "cpuacct.usage", "34420000000\n")
    put(proc / "stat", "cpu  0 0 0 0 0 0 0 0 0 0\ncpu0 0 0 0 0 0 0 0 0 0 0\n")
    host = HostStat(str(proc), str(root))
    assert host.keys == () and host.sample() == {}


def test_nothing_readable_gives_an_empty_sample(tmp_path):
    host = HostStat(str(tmp_path / "none"), str(tmp_path / "none"))
    assert host.keys == () and host.sample() == {}


def test_a_file_that_turns_unreadable_drops_its_keys_from_the_sample(v2):
    proc, root = v2
    host = HostStat(str(proc), str(root))
    os.remove(proc / "stat")
    os.makedirs(proc / "stat")  # reading a directory raises
    cg = root / "app.slice" / "node.scope"
    os.remove(cg / "memory.pressure")
    s = host.sample()
    for gone in ("cpu_steal", "memory_some", "memory_full"):
        assert gone not in s
    assert s["cpu_throttled"] == pytest.approx(1.5)
    assert "cpu_steal" in host.keys  # what it could read at start


@pytest.mark.parametrize("text, expected", [
    (PSI_FULL.format(some=1500000, full=250000),
     {"memory_some": 1.5, "memory_full": 0.25}),
    ("some avg10=1.00 avg60=0.50 avg300=0.10 total=42\n",
     {"memory_some": 4.2e-5}),
    ("garbage\nfull total=notanumber\n", {}),
])
def test_psi_lines(text, expected):
    assert hoststat.parse_psi("memory", text) == pytest.approx(expected)


def test_steal_is_the_average_cpus_and_all_zero_accounting_is_absent():
    assert hoststat.parse_proc_stat(100, PROC_STAT.replace(
        f" {4 * CLK_TCK} ", " 800 "
    )) == pytest.approx({"cpu_steal": 2.0})  # 800 ticks / 100 / 4 CPUs
    assert hoststat.parse_proc_stat(100, "cpu  0 0 0 0 0 0 0 0 0 0\n") == {}
    assert hoststat.parse_proc_stat(100, "intr 1 2 3\n") == {}
