"""Unit tests for config, resource parsing, shard API, partition search."""

import numpy as np
import pytest

from parallax_tpu import shard as shard_lib
from parallax_tpu.common import consts
from parallax_tpu.common.config import (CheckPointConfig, MPIConfig,
                                        ParallaxConfig, PSConfig,
                                        normalize_run_option)
from parallax_tpu.common.lib import (HostInfo, deserialize_resource_info,
                                     parse_resource_info,
                                     serialize_resource_info)
from parallax_tpu.parallel.partitions import PartitionSearch, divisors


class TestConfig:
    def test_defaults_match_reference_schema(self):
        cfg = ParallaxConfig()
        assert cfg.run_option == "HYBRID"
        assert cfg.average_sparse is False
        assert cfg.search_partitions is True
        assert cfg.communication_config.ps_config.protocol == "grpc"
        assert cfg.communication_config.mpi_config.mpirun_options == ""
        assert cfg.ckpt_config.ckpt_dir is None
        assert cfg.profile_config.profile_dir is None

    def test_legacy_run_option_aliases(self):
        assert normalize_run_option("MPI") == "AR"
        assert normalize_run_option("PS") == "SHARD"
        assert normalize_run_option("hybrid") == "HYBRID"
        assert ParallaxConfig(run_option="MPI").run_option == "AR"
        with pytest.raises(ValueError):
            normalize_run_option("NCCL")

    def test_setters(self):
        cfg = ParallaxConfig()
        cfg.set_sync(False)
        assert cfg.sync is False
        cfg.set_resource_info([HostInfo("h")])
        assert cfg.resource_info[0].hostname == "h"

    def test_unused_knobs_surfaced(self):
        cfg = ParallaxConfig()
        cfg.communication_config.ps_config.protocol = "grpc+verbs"
        cfg.communication_config.mpi_config.mpirun_options = "-x FOO"
        assert set(cfg.unused_knobs()) == {
            "communication_config.ps_config.protocol",
            "communication_config.mpi_config.mpirun_options"}


class TestResourceInfo:
    def test_parse_literal_with_devices(self):
        hosts = parse_resource_info("10.0.0.1: 0,1,2,3\n10.0.0.2: 4,5")
        assert hosts == [HostInfo("10.0.0.1", (0, 1, 2, 3)),
                         HostInfo("10.0.0.2", (4, 5))]

    def test_parse_bare_host_and_comments(self):
        hosts = parse_resource_info("# cluster\nhostA\nhostB: 0 1\n")
        assert hosts[0] == HostInfo("hostA")
        assert hosts[1] == HostInfo("hostB", (0, 1))

    def test_parse_file(self, tmp_path):
        f = tmp_path / "resource_info"
        f.write_text("localhost: 0,1\n")
        assert parse_resource_info(str(f)) == [HostInfo("localhost", (0, 1))]

    def test_duplicate_host_rejected(self):
        with pytest.raises(ValueError):
            parse_resource_info("a\na")

    def test_serialization_roundtrip(self):
        hosts = [HostInfo("a", (0, 1)), HostInfo("b")]
        assert deserialize_resource_info(
            serialize_resource_info(hosts)) == hosts

    def test_none_defaults_to_localhost(self):
        assert parse_resource_info(None) == [HostInfo("localhost")]


class TestIsLocalHost:
    def test_loopback_literals(self):
        from parallax_tpu.common.lib import is_local_host
        assert is_local_host("localhost")
        assert is_local_host("127.0.0.1")
        # whole 127/8 network: the N-process CPU rigs name
        # 127.0.0.2/127.0.0.3/... for distinct local workers
        assert is_local_host("127.0.0.2")
        assert is_local_host("::1")

    def test_hostname_that_merely_starts_with_127_is_not_loopback(self):
        from parallax_tpu.common.lib import is_local_host
        # ADVICE r5: "127.example.com" is a resolvable NAME, not an IP
        # literal — it must take the resolver path, not the shortcut
        assert not is_local_host("127.example.com")
        assert not is_local_host("10.0.0.1")

    def test_own_hostname_is_local(self):
        import socket
        from parallax_tpu.common.lib import is_local_host
        assert is_local_host(socket.gethostname())


class TestShardAPI:
    def test_mod_filter_semantics(self):
        # reference shard.py:69-87: elem index % num_shards == shard_id
        data = list(range(10))
        assert list(shard_lib.shard(data, num_shards=3, shard_id=0)) == [
            0, 3, 6, 9]
        assert list(shard_lib.shard(data, num_shards=3, shard_id=2)) == [
            2, 5, 8]

    def test_install_and_defaults(self):
        shard_lib._install(4, 1)
        assert shard_lib.create_num_shards_and_shard_id() == (4, 1)
        assert list(shard_lib.shard(range(8))) == [1, 5]
        shard_lib._install(1, 0)

    def test_bad_shard_id(self):
        with pytest.raises(ValueError):
            shard_lib._install(2, 5)


class TestPartitionSearch:
    def test_divisors(self):
        assert divisors(8) == [1, 2, 4, 8]

    def test_doubling_until_worse_then_fit(self):
        s = PartitionSearch(1, 8)
        assert s.first_candidate() == 1
        assert s.report(1, 1.0) == 2
        assert s.report(2, 0.6) == 4
        assert s.report(4, 0.5) == 8
        assert s.report(8, 0.7) is None  # worse -> stop
        best = s.best_partitions()
        assert best in (2, 4)  # argmin of the fitted curve

    def test_curve_fit_matches_known_model(self):
        # t(p) = b/p + a(p-1) + c with known coefficients: minimum at
        # sqrt(b/a); for b=0.8, a=0.05 -> p* = 4.
        a, b, c = 0.05, 0.8, 0.1
        s = PartitionSearch(1, 8)
        p = s.first_candidate()
        while True:
            t = b / p + a * (p - 1) + c
            nxt = s.report(p, t)
            if nxt is None:
                break
            p = nxt
        assert s.best_partitions() == 4

    def test_min_partitions_snapped_to_divisor(self):
        s = PartitionSearch(3, 8)
        assert s.first_candidate() == 2


class TestSliceAwareMesh:
    """build_mesh orders devices so shard rings stay inside one
    connectivity domain (TPU slice / host) and 'repl' crosses domains
    (DCN) — the topology split behind the two-stage sparse combine."""

    class FakeDev:
        def __init__(self, i, slice_index):
            self.id = i
            self.slice_index = slice_index
            self.process_index = 0

        def __repr__(self):
            return f"d{self.id}s{self.slice_index}"

    def _devs(self, interleaved=True):
        # 8 devices over 2 slices, enumerated slice-interleaved (worst
        # case: naive order would put both slices in every shard ring)
        if interleaved:
            order = [0, 1, 0, 1, 0, 1, 0, 1]
        else:
            order = [0, 0, 0, 0, 1, 1, 1, 1]
        return [self.FakeDev(i, s) for i, s in enumerate(order)]

    def test_shard_ring_nests_in_slice(self):
        from parallax_tpu.core.mesh import _order_by_domain
        devs = self._devs(interleaved=True)
        ordered = _order_by_domain(devs, p=4)
        rows = [ordered[0:4], ordered[4:8]]
        for row in rows:
            assert len({d.slice_index for d in row}) == 1

    def test_non_nesting_shard_count_warns_keeps_order(self):
        from parallax_tpu.core.mesh import _order_by_domain
        # 8 devices over 2 slices of 4; p=8 spans both (8 % 4 == 0 ->
        # still grouped so repl rows align); p=3 can't nest at all
        devs = self._devs(interleaved=True)
        assert len(_order_by_domain(devs, p=8)) == 8
        ordered = _order_by_domain(devs, p=3)
        assert [d.id for d in ordered] == list(range(8))

    def test_single_domain_untouched(self):
        from parallax_tpu.core.mesh import _order_by_domain
        devs = self._devs(interleaved=False)
        for d in devs:
            d.slice_index = 0
        ordered = _order_by_domain(devs, p=4)
        assert [d.id for d in ordered] == list(range(8))

    def test_unequal_domains_still_nest_when_divisible(self):
        from parallax_tpu.core.mesh import _order_by_domain
        # 12 devices over slices of 8 and 4; p=4 splits both into whole
        # rings -> grouped despite unequal sizes
        devs = ([self.FakeDev(i, 0) for i in range(8)]
                + [self.FakeDev(8 + i, 1) for i in range(4)])
        import random
        random.Random(0).shuffle(devs)
        ordered = _order_by_domain(devs, p=4)
        for row in range(3):
            ring = ordered[row * 4:(row + 1) * 4]
            assert len({d.slice_index for d in ring}) == 1
