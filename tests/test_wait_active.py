"""`Cluster.wait_active`: the gate a degraded deployment waits at —
every PG active, none peering or down, undersized and degraded
allowed — beside `wait_active_clean`, which an OSD down can never
pass."""

import pytest

from ceph_tpu.tools.vstart import Cluster


@pytest.fixture(scope="module")
def cluster():
    with Cluster(n_osds=6) as c:
        client = c.client()
        client.set_ec_profile("k4m2", {
            "plugin": "jax", "technique": "cauchy", "k": "4", "m": "2",
            "stripe_unit": "4096"})
        client.create_pool("ec", "erasure",
                           erasure_code_profile="k4m2", pg_num=2)
        client.create_pool("rep", "replicated", size=3, pg_num=2)
        c.wait_active_clean(timeout=120)
        yield c, client


def test_a_clean_cluster_is_active(cluster):
    c, _ = cluster
    c.wait_active(timeout=30)


def test_active_with_one_osd_down_and_never_clean(cluster):
    c, client = cluster
    io = client.open_ioctx("ec")
    io.write_full("obj", b"x" * 20000)
    c.kill_osd(5)
    c.mark_osd_down(5)
    c.wait_active(timeout=60)
    # k+m = 6 on 6 OSDs: every EC PG has a hole now and serves
    io.write("obj", b"y" * 4096, 4096)
    assert io.read("obj") == b"x" * 4096 + b"y" * 4096 \
        + b"x" * (20000 - 8192)
    with pytest.raises(TimeoutError, match="osd.5 down"):
        c.wait_active_clean(timeout=1.5, stable_for=0.2)


def test_a_pg_under_min_size_is_down_not_active(cluster):
    c, _ = cluster
    c.kill_osd(4)
    c.mark_osd_down(4)
    # four live shards of six: under min_size = k+1
    with pytest.raises(TimeoutError, match="not active within.*4/5"):
        c.wait_active(timeout=3.0, stable_for=0.2)
    c.revive_osd(4)
    c.wait_active(timeout=60)
    c.revive_osd(5)
    c.wait_active_clean(timeout=120)
