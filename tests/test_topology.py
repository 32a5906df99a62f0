"""AOT topology validation (runtime/topology.py): named TPU topologies
build without hardware, multi-chip programs compile against the REAL TPU
pipeline, and the bf16-psum-in-manual-region gate's evidence holds — bf16
manual wires compile clean at half the f32 operand bytes.

The fast tests here compile only the isolated psum probe (seconds); the
full program registry (ring-flash, llama dp x tp, both 1F1B manual-tp
schedules — the TOPOLOGY_r06.json sweep) is the ``slow``-marked test.
"""

import json

import pytest

from torchmpi_tpu.runtime import topology


@pytest.fixture(scope="module")
def v5e():
    try:
        devs = topology.topology_devices("v5e-8")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"TPU topology descriptions unavailable: {e!r}")
    return devs


class TestTopologyDescriptions:
    def test_known_topologies_registered(self):
        assert set(topology.TOPOLOGIES) >= {"v5e-8", "v4-32"}

    def test_v5e_devices(self, v5e):
        assert len(v5e) == 8
        assert "v5" in v5e[0].device_kind.lower()

    def test_mesh_over_topology(self, v5e):
        mesh = topology.topology_mesh("v5e-8", {"dp": -1, "tp": 4})
        assert dict(mesh.shape) == {"dp": 2, "tp": 4}


class TestHloCollectiveStats:
    """Fixtures are in the installed JAX's HLO text: operands are named
    without their types, so the parser resolves each to its definition."""

    def test_operand_dtype_and_bytes(self):
        hlo = (
            "  %fusion.1 = f32[8,256]{1,0:T(8,128)S(1)} fusion(%p0), "
            "kind=kLoop, calls=%fused_computation\n"
            "  %all-reduce.1 = f32[8,256]{1,0:T(8,128)S(1)} "
            "all-reduce(%fusion.1), channel_id=1, "
            "replica_groups={{0,1},{2,3}}, metadata={op_name=\"a(b)\"}\n"
            "  %x = bf16[4]{0} parameter(0)\n"
            "  %ag = bf16[16]{0} all-gather(%x), dimensions={0}\n"
            "  %s = f32[8]{0} all-reduce-start(%fusion.1)\n"
            "  %ard = f32[8]{0} all-reduce-done(%s)\n"
        )
        stats = topology.hlo_collective_stats(hlo)
        # -start folds onto the base opcode, -done does not double count.
        assert stats["counts"] == {"all-reduce:f32": 2,
                                   "all-gather:bf16": 1}
        assert stats["operand_bytes"]["all-reduce:f32"] == 2 * 8 * 256 * 4
        # The OPERAND is what rides the wire, not the 4x gathered result.
        assert stats["operand_bytes"]["all-gather:bf16"] == 4 * 2

    def test_tuple_operands_sum(self):
        hlo = ("  %a = bf16[4]{0} parameter(0)\n"
               "  %b = bf16[8]{0} parameter(1)\n"
               "  ROOT %ar = (bf16[4]{0:T(8,128)(2,1)}, /*index=1*/"
               "bf16[8]{0}) all-reduce(%a, %b), channel_id=1\n")
        stats = topology.hlo_collective_stats(hlo)
        assert stats["counts"] == {"all-reduce:bf16": 1}
        assert stats["operand_bytes"]["all-reduce:bf16"] == (4 + 8) * 2


class TestManualPsumGate:
    """The evidence behind ``manual_wire_dtype="auto"`` resolving to bf16
    on TPU: both wire dtypes compile in a manual region against the real
    TPU pipeline, and the bf16 wire moves half the bytes."""

    @pytest.fixture(scope="class")
    def records(self, v5e):
        out = topology.dryrun_topology(
            "v5e-8", programs=["manual_psum_f32", "manual_psum_bf16"])
        return out["programs"]

    def test_both_wires_compile(self, records):
        assert records["manual_psum_f32"]["compile_ok"], records
        assert records["manual_psum_bf16"]["compile_ok"], records

    def test_wire_dtypes_in_hlo(self, records):
        f32 = records["manual_psum_f32"]["collectives"]["counts"]
        bf16 = records["manual_psum_bf16"]["collectives"]["counts"]
        assert any(k.startswith("all-reduce:f32") for k in f32), f32
        assert any(k.startswith("all-reduce:bf16") for k in bf16), bf16

    def test_bf16_wire_halves_bytes(self, records):
        def ar_bytes(rec):
            return sum(v for k, v in
                       rec["collectives"]["operand_bytes"].items()
                       if k.startswith("all-reduce"))

        f32 = ar_bytes(records["manual_psum_f32"])
        bf16 = ar_bytes(records["manual_psum_bf16"])
        assert bf16 > 0 and f32 == 2 * bf16, (f32, bf16)

    def test_memory_stats_recorded(self, records):
        mem = records["manual_psum_bf16"].get("memory")
        assert mem and mem["peak_hbm_bytes"] > 0


@pytest.mark.slow
class TestFullProgramRegistry:
    """The TOPOLOGY_r06.json sweep shape: every registered program AOT-
    compiles (or records its compiler verdict) against v5e-8.  Minutes of
    compile time — the CI fast loop runs the psum probes above instead."""

    def test_dryrun_v5e8_all_programs(self, v5e):
        out = topology.dryrun_topology("v5e-8", wire_dtype="bfloat16")
        assert out["chips"] == 8
        # Every registered program must compile clean — including the
        # pallas ring kernels, whose AOT build forces interpret OFF so
        # Mosaic (not the CPU interpreter) judges the remote
        # DMA/semaphore code.
        for label, rec in out["programs"].items():
            assert rec["compile_ok"], (label, rec.get("error"))
        assert out["compile_ok_count"] == len(topology.PROGRAMS)
        # Artifact shape: serializable as-is.
        json.dumps(out)
