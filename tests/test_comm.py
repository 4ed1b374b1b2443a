"""repro.comm: wire codec round trips, exact bit-parity with the analytic
message_bits model, frame protocol, loopback star runs reproducing the
single-node run_fednl trajectory, and the TCP-localhost multi-process run."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import protocol, wire
from repro.comm.cost import CommCostModel
from repro.comm.star import run_loopback
from repro.comm.transport import loopback_pair
from repro.compressors import get_compressor
from repro.compressors.core import message_bits
from repro.core import FedNLConfig, run_fednl
from repro.data import add_intercept, make_synthetic_logreg, partition_clients

ALL_COMPRESSORS = ["identity", "topk", "randk", "randseqk", "toplek", "natural"]

LAM = 1e-3


def _rand_u(seed, t, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), (t,), dtype=jnp.float64) * scale


@pytest.fixture(scope="module")
def z():
    x, y = make_synthetic_logreg("tiny", seed=1)
    return jnp.asarray(partition_clients(add_intercept(x), y, 8, 40, seed=1))


# ---------------------------------------------------------------------------
# codec round trips (satellite: decode(encode(m)) == m for all six)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_COMPRESSORS)
@pytest.mark.parametrize("t,k,seed,scale", [
    (300, 37, 0, 1.0),
    (55, 1, 1, 1e-6),
    (10, 10, 2, 1e8),
    (496, 128, 3, 1e-3),
])
def test_codec_roundtrip_matches_dense_compressor(name, t, k, seed, scale):
    """decode(encode(key, u)) must equal comp.compress(key, u)[0] BIT-exactly
    — including RandK/RandSeqK seed-reconstruction and Natural's replayed
    sign+exponent format (this is what makes a TCP run reproduce the
    simulation trajectory)."""
    u = _rand_u(seed, t, scale)
    key = jax.random.PRNGKey(seed + 1000)
    comp = get_compressor(name, t, k)
    codec = wire.make_codec(comp, t)
    enc = codec.encode(key, u)
    dec = codec.decode(enc.data, enc.sent_elems)
    dense, _ = comp.compress(key, u)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(dense))


@pytest.mark.parametrize("name", ALL_COMPRESSORS)
@pytest.mark.parametrize("t,k,seed", [(300, 37, 0), (45, 45, 1), (128, 5, 2)])
def test_codec_bits_match_analytic_model(name, t, k, seed):
    """Measured encoded bits == message_bits(comp, sent_elems), and the byte
    buffer is exactly the bit count rounded up (Natural is bit-packed)."""
    u = _rand_u(seed, t)
    comp = get_compressor(name, t, k)
    codec = wire.make_codec(comp, t)
    enc = codec.encode(jax.random.PRNGKey(seed), u)
    assert enc.bits == int(message_bits(comp, jnp.asarray(enc.sent_elems)))
    assert len(enc.data) == (enc.bits + 7) // 8


def test_randseqk_seed_reconstruction_equality():
    """Only a 32-bit start index travels; the receiver rebuilds the window."""
    t, k = 210, 17
    u = _rand_u(5, t)
    comp = get_compressor("randseqk", t, k)
    codec = wire.make_codec(comp, t)
    key = jax.random.PRNGKey(9)
    enc = codec.encode(key, u)
    assert len(enc.data) == 4 + 8 * k  # u32 start + k FP64 values, nothing else
    np.testing.assert_array_equal(
        np.asarray(codec.decode(enc.data, k)), np.asarray(comp.compress(key, u)[0])
    )


def test_randk_wire_carries_no_indices():
    t, k = 210, 17
    comp = get_compressor("randk", t, k)
    codec = wire.make_codec(comp, t)
    enc = codec.encode(jax.random.PRNGKey(3), _rand_u(6, t))
    assert len(enc.data) == 8 + 8 * k  # 64-bit PRG key + values only


def test_natural_exponent_only_lossiness_bound():
    """The 12-bit format is exact on the compressor OUTPUT; vs the original
    vector the loss is the power-of-two rounding itself: ratio in (1/2, 2]
    times the 8/9 scale."""
    t = 400
    u = _rand_u(7, t, scale=1e-2)
    comp = get_compressor("natural", t, 0)
    codec = wire.make_codec(comp, t)
    enc = codec.encode(jax.random.PRNGKey(8), u)
    dec = np.asarray(codec.decode(enc.data, t))
    u_np = np.asarray(u)
    nz = u_np != 0
    ratio = np.abs(dec[nz] / u_np[nz])
    lo, hi = wire.NATURAL_SCALE / 2, wire.NATURAL_SCALE * 2
    assert (ratio > lo - 1e-12).all() and (ratio <= hi + 1e-12).all()
    assert np.sign(dec[nz]).tolist() == np.sign(u_np[nz]).tolist()
    assert enc.bits == 12 * t


# ---------------------------------------------------------------------------
# protocol framing
# ---------------------------------------------------------------------------

def test_frame_pack_unpack_roundtrip():
    a, b = loopback_pair()
    frame = protocol.Frame(
        type=protocol.MsgType.UPLINK, round=7, client=3, comp_id=4,
        sent_elems=12, payload_bits=1184, payload=b"\x01\x02\x03",
    )
    sent = protocol.send_frame(a, frame)
    assert sent == protocol.HEADER_SIZE + 3
    got = protocol.recv_frame(b)
    assert got == frame


def test_frame_rejects_bad_magic():
    with pytest.raises(ValueError, match="magic"):
        protocol.unpack_header(b"XXXX" + bytes(protocol.HEADER_SIZE - 4))


def test_uplink_payload_roundtrip():
    d = 11
    grad = _rand_u(1, d)
    enc = wire.EncodedMessage(b"\xaa" * 9, 72, 3)
    payload = protocol.pack_uplink(grad, 0.25, 1.5, enc)
    g2, l2, f2, hess = protocol.unpack_uplink(payload, d)
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(grad))
    assert float(l2) == 0.25 and float(f2) == 1.5 and hess == enc.data


@pytest.mark.parametrize("name", ALL_COMPRESSORS)
def test_frame_bits_model_matches_real_frame(name):
    """wire.frame_bits (the FedNLConfig accounting='wire' model) equals the
    byte length of an actually-assembled UPLINK frame."""
    t, k, d = 78, 9, 12
    comp = get_compressor(name, t, k)
    codec = wire.make_codec(comp, t)
    enc = codec.encode(jax.random.PRNGKey(0), _rand_u(2, t))
    frame = protocol.Frame(
        type=protocol.MsgType.UPLINK, sent_elems=enc.sent_elems,
        payload_bits=enc.bits,
        payload=protocol.pack_uplink(_rand_u(3, d), 0.0, 0.0, enc),
    )
    assert 8 * frame.wire_bytes == int(wire.frame_bits(comp, enc.sent_elems, d))


# ---------------------------------------------------------------------------
# star topology: loopback end-to-end vs the single-node simulation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comp", ["topk", "randseqk", "natural"])
def test_loopback_reproduces_single_node_trajectory(z, comp):
    """The full encode->frame->decode star run must track run_fednl to <=1e-8
    (in practice it is bit-identical — same oracles, same PRG schedule, exact
    codecs, same jnp aggregation)."""
    cfg = FedNLConfig(compressor=comp, lam=LAM)
    ref = run_fednl(z, cfg, rounds=12, seed=0)
    lb = run_loopback(z, cfg, rounds=12, seed=0)
    np.testing.assert_allclose(lb.x, ref.x, atol=1e-8)
    np.testing.assert_allclose(lb.grad_norms, ref.grad_norms, atol=1e-8)
    np.testing.assert_allclose(lb.f_vals, ref.f_vals, atol=1e-8)
    assert lb.grad_norms[-1] < 1e-10  # still converges through the wire


@pytest.mark.parametrize("comp", ALL_COMPRESSORS)
def test_loopback_measured_bits_equal_analytic(z, comp):
    """Acceptance: measured wire bytes == the analytic message_bits model for
    every compressor, and the framed bytes match the frame_bits model."""
    cfg = FedNLConfig(compressor=comp, lam=LAM)
    lb = run_loopback(z, cfg, rounds=2, seed=0)
    np.testing.assert_array_equal(lb.measured_payload_bits, lb.sent_bits)
    # cross-check against the jitted simulation's analytic accounting
    ref = run_fednl(z, cfg, rounds=2, seed=0)
    np.testing.assert_array_equal(ref.sent_bits.astype(np.int64), lb.sent_bits)


def test_wire_accounting_option_matches_measured_frames(z):
    """FedNLConfig(accounting='wire') makes the simulation's sent_bits equal
    the real framed byte stream of the transport run."""
    cfg = FedNLConfig(compressor="toplek", lam=LAM, accounting="wire")
    ref = run_fednl(z, cfg, rounds=3, seed=0)
    lb = run_loopback(z, dataclasses.replace(cfg, accounting="payload"),
                      rounds=3, seed=0)
    np.testing.assert_array_equal(
        ref.sent_bits.astype(np.int64), 8 * lb.measured_frame_bytes
    )


def test_loopback_hess0_zero_cold_start(z):
    cfg = FedNLConfig(compressor="topk", lam=LAM, hess0="zero")
    ref = run_fednl(z, cfg, rounds=10, seed=0)
    lb = run_loopback(z, cfg, rounds=10, seed=0)
    np.testing.assert_allclose(lb.grad_norms, ref.grad_norms, atol=1e-8)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_cost_model_round_time():
    cm = CommCostModel(bandwidth_bps=1e9, latency_s=1e-4)
    # 8 clients x 1 Mbit uplink + 1 Mbit broadcast = 9 ms wire + 2 latencies
    got = cm.round_s(8e6, 1e6, n_clients=8)
    assert got == pytest.approx(2e-4 + 9e-3)
    # parallel-uplink variant is bounded by one client's share
    cm_p = CommCostModel(bandwidth_bps=1e9, latency_s=1e-4, master_shared_nic=False)
    assert cm_p.round_s(8e6, 1e6, n_clients=8) == pytest.approx(2e-4 + 2e-3)


def test_star_roofline_dominance():
    from repro.roofline import star_roofline

    r = star_roofline(1e-3, 8e9, 1e6, n_clients=8)  # 8 Gbit uplink: comm-bound
    assert r["dominant"] == "comm" and r["round_s"] >= r["comm_s"]
    r2 = star_roofline(1.0, 8e3, 1e3, n_clients=8)
    assert r2["dominant"] == "compute"


def test_machine_for_device_kind():
    """Roofline peaks come from a table keyed by device_kind; a device that
    is not in it is an error, never a v5e default."""
    from repro.roofline import TPU_V5E, machine_for

    assert machine_for("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no roofline peaks"):
        machine_for("cpu")


# ---------------------------------------------------------------------------
# TCP localhost, real client processes (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.net
def test_tcp_multiproc_reproduces_single_node_trajectory():
    """master + n client processes over TCP localhost track run_fednl <=1e-8."""
    from repro.launch.multiproc import _build_problem, run_multiproc

    shape = (16, 4, 30)  # d, n_clients, n_i — small: 4 jax client processes
    cfg = FedNLConfig(compressor="topk", lam=LAM)
    try:
        res = run_multiproc(cfg, shape=shape, rounds=8, seed=0)
    except (OSError, PermissionError) as e:  # pragma: no cover
        pytest.skip(f"multiprocess TCP unavailable in this sandbox: {e}")
    z = _build_problem("", shape, 0)
    ref = run_fednl(z, cfg, rounds=8, seed=0)
    np.testing.assert_allclose(res.x, ref.x, atol=1e-8)
    np.testing.assert_allclose(res.grad_norms, ref.grad_norms, atol=1e-8)
    np.testing.assert_array_equal(res.measured_payload_bits, res.sent_bits)
