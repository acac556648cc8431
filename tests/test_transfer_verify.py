"""The P->D transfer check: a bit-exact compare of the moved pages on the
device, one flag read back (``core/transfer.py`` ``verify_transfer`` /
``verify_sharded_transfer``).

Every fault below lands in a page the plan moved and must be caught, in
both the FLOWKV->FLOWKV and the FLOWKV->VLLM layout pairings; what the plan
did not move is not checked; the page tables are padded to a power of two
without masking a fault; and no pool crosses to the host.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import layout as L
from repro.core import transfer as T
from repro.core.transfer import (ShardedTransferEngine, ShardSpec,
                                 TransferEngine, check_bucket,
                                 verify_sharded_transfer, verify_transfer)

ArrayImpl = type(jnp.zeros(()))   # the concrete jax.Array class
LAYOUTS = {"flowkv->flowkv": L.KVLayout.FLOWKV, "flowkv->vllm": L.KVLayout.VLLM}
# 3 blocks x 3 layers x K/V = 18 pages: a plan whose page count is not a
# power of two, so the check pads it (to 32)
SRC_BLOCKS, DST_BLOCKS = [2, 5, 6], [9, 1, 4]
FREE_BLOCK = 11            # a dst block the plan does not write


def _spec(layout=L.KVLayout.FLOWKV, num_blocks=16):
    return L.KVCacheSpec(num_layers=3, num_blocks=num_blocks, block_size=4,
                         num_kv_heads=2, head_dim=8, dtype=jnp.bfloat16,
                         layout=layout)


def _pool(spec, seed):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*spec.shape), spec.dtype)


def _landed(dst_layout, src_blocks=SRC_BLOCKS, dst_blocks=DST_BLOCKS):
    """A plan executed between two random pools: (plan, specs, pools)."""
    src_spec, dst_spec = _spec(), _spec(dst_layout)
    engine = TransferEngine(src_spec, dst_spec)
    plan = engine.planner.plan("flowkv", src_blocks, dst_blocks)
    src = _pool(src_spec, 0)
    dst = engine.execute(plan, src, _pool(dst_spec, 1))
    return plan, src_spec, src, dst_spec, dst


def _flip_bit(flat, page, elem):
    bits = flat.view(np.uint16)
    bits[page, elem] ^= np.uint16(1 << 3)


def _fault(name, flat, ids, stale):
    """Damage one page the plan moved, in the dst pool's flat page view."""
    if name == "flip_first":
        _flip_bit(flat, ids[0], 0)
    elif name == "flip_last":           # the real last pair, before padding
        _flip_bit(flat, ids[-1], flat.shape[1] - 1)
    elif name == "swap":
        flat[[ids[0], ids[1]]] = flat[[ids[1], ids[0]]]
    elif name == "wrong_slot":          # landed in a slot the plan did not name
        flat[FREE_BLOCK] = flat[ids[-1]]
        flat[ids[-1]] = stale[ids[-1]]


@pytest.mark.parametrize("step_pages", [None, 4],
                         ids=["one_step", "4_pages_a_step"])
@pytest.mark.parametrize("fault", ["flip_first", "flip_last", "swap",
                                   "wrong_slot"])
@pytest.mark.parametrize("layouts", list(LAYOUTS))
def test_fault_in_a_moved_page_is_caught(monkeypatch, layouts, fault,
                                         step_pages):
    plan, src_spec, src, dst_spec, dst = _landed(LAYOUTS[layouts])
    if step_pages:                      # the compare loop takes 8 steps
        monkeypatch.setattr(T, "_CHECK_STEP_BYTES",
                            step_pages * src.nbytes // src.size
                            * src_spec.payload)
    pages = len(plan.to_descriptors())
    assert check_bucket(pages) > pages        # the padding is exercised
    assert verify_transfer(plan, src_spec, src, dst_spec, dst)
    ids = plan.to_descriptors().page_ids(dst_spec, "dst")
    flat = np.array(dst).reshape(-1, dst_spec.payload)
    stale = np.asarray(_pool(dst_spec, 1)).reshape(-1, dst_spec.payload)
    _fault(fault, flat, ids, stale)
    bad = jnp.asarray(flat.reshape(dst_spec.shape))
    assert not verify_transfer(plan, src_spec, src, dst_spec, bad)


@pytest.mark.parametrize("layouts", list(LAYOUTS))
def test_pages_outside_the_plan_are_not_checked(layouts):
    plan, src_spec, src, dst_spec, dst = _landed(LAYOUTS[layouts])
    moved = set(plan.to_descriptors().page_ids(dst_spec, "dst").tolist())
    flat = np.array(dst).reshape(-1, dst_spec.payload)
    others = [p for p in range(flat.shape[0]) if p not in moved]
    flat[others] += 1
    src_flat = np.array(src).reshape(-1, src_spec.payload)
    src_moved = set(plan.to_descriptors().page_ids(src_spec, "src").tolist())
    src_flat[[p for p in range(src_flat.shape[0]) if p not in src_moved]] -= 1
    assert verify_transfer(plan, src_spec,
                           jnp.asarray(src_flat.reshape(src_spec.shape)),
                           dst_spec, jnp.asarray(flat.reshape(dst_spec.shape)))


def test_empty_plan_verifies():
    spec = _spec()
    plan = TransferEngine(spec).planner.plan("flowkv", [], [])
    assert len(plan.to_descriptors()) == 0
    assert verify_transfer(plan, spec, _pool(spec, 0), spec, _pool(spec, 1))


@pytest.mark.parametrize("value,other", [
    (float("nan"), float("nan")),       # same NaN payload: equal bits
    (0.0, -0.0),                        # equal values, different bits
])
def test_compare_is_on_bits_not_values(value, other):
    plan, src_spec, src, dst_spec, dst = _landed(L.KVLayout.FLOWKV)
    sid = int(plan.to_descriptors().page_ids(src_spec, "src")[0])
    did = int(plan.to_descriptors().page_ids(dst_spec, "dst")[0])
    src = src.reshape(-1, src_spec.payload).at[sid, 0].set(value) \
        .reshape(src_spec.shape)
    dst = dst.reshape(-1, dst_spec.payload).at[did, 0].set(other) \
        .reshape(dst_spec.shape)
    same_bits = np.float32(value).tobytes() == np.float32(other).tobytes()
    assert verify_transfer(plan, src_spec, src, dst_spec, dst) == same_bits


def test_plans_of_one_bucket_share_one_program():
    # a pool size no other test uses, so this test's compiles are its own
    src_spec = dst_spec = _spec(num_blocks=21)
    engine = TransferEngine(src_spec)
    src, dst = _pool(src_spec, 2), _pool(dst_spec, 3)
    before = T._rows_equal._cache_size()
    for src_blocks, dst_blocks in (([0, 1, 2], [3, 4, 5]),     # 18 pages
                                   ([7, 9, 11, 13, 15], [1, 2, 3, 4, 5])):
        plan = engine.planner.plan("flowkv", src_blocks, dst_blocks)
        assert check_bucket(len(plan.to_descriptors())) == 32
        dst = engine.execute(plan, src, dst)
        assert verify_transfer(plan, src_spec, src, dst_spec, dst)
    assert T._rows_equal._cache_size() == before + 1


def _sharded_landed():
    spec = L.KVCacheSpec(num_layers=2, num_blocks=8, block_size=4,
                         num_kv_heads=4, head_dim=8, dtype=jnp.bfloat16)
    engine = ShardedTransferEngine(spec, spec, ShardSpec(2, 4), ShardSpec(1, 4))
    shard_spec = T.shard_slice_spec(spec, ShardSpec(2, 4))
    src = [_pool(shard_spec, 4), _pool(shard_spec, 5)]
    plan = engine.plan("flowkv", [0, 3, 5], [6, 2, 1])
    dst = engine.execute(plan, src, [_pool(spec, 6)])
    return plan, spec, src, dst


@pytest.mark.parametrize("sharded", [False, True], ids=["unsharded", "sharded"])
def test_check_copies_no_pool_to_the_host(monkeypatch, sharded):
    """Only the flag crosses to the host. The transfer guard enforces it on
    an accelerator; the CPU backend does not enforce it, so every host read
    of a device array is also recorded, and must be of one element."""
    if sharded:
        plan, spec, src, dst = _sharded_landed()
        check = lambda: verify_sharded_transfer(plan, spec, src, spec, dst)
    else:
        plan, src_spec, src, dst_spec, dst = _landed(L.KVLayout.VLLM)
        check = lambda: verify_transfer(plan, src_spec, src, dst_spec, dst)
    read = []
    value = ArrayImpl._value

    def recorded(self):
        read.append(self.size)
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(recorded))
    with jax.transfer_guard_device_to_host("disallow"):
        assert check()
    assert read == [1]
