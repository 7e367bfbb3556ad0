"""The blocked SpMV launch format's one padding rule (``repro.sparse.tensor``,
DESIGN.md §9): a member padded to its own bucket edges, a bucket stacked on
the host and a bucket stacked on the device from the store's prepared
members all pad through ``pad_arrays``, and so agree array for array."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autotune import SELL_SIGMA, Schedule
from repro.core.synthetic import gen_zipf
from repro.sparse import PreparedStore, bucket_edge, content_key
from repro.sparse.ops_builtin import _build_matvec_bucket
from repro.sparse.tensor import (LAUNCH_FIELDS, SparseTensor, bucket_target,
                                 container_arrays, pad_arrays,
                                 pad_container_to_bucket)

LAYOUTS = ["ell", "sell", "dense"]
SIZES = (96, 200, 340)       # 6, 13 and 22 block rows at bs 16: 3 edges


def _schedule(layout):
    if layout == "dense":
        return Schedule("dense", 16, 1.0)
    if layout == "sell":
        return Schedule("bsr", 16, 1.0, layout="sell", slice_height=4)
    return Schedule("bsr", 16, 1.0)


def _members():
    return [gen_zipf(n, seed=60 + i) for i, n in enumerate(SIZES)]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_host_and_resident_bucket_stacks_agree(layout):
    """A bucket of three members of different sizes, stacked with no store
    (host containers, padded on the host) and with a store plus member
    keys (the store's device-resident tensors, padded on the device)."""
    mats, sched = _members(), _schedule(layout)
    host = _build_matvec_bucket(mats, sched, SELL_SIGMA)
    store = PreparedStore()
    resident = _build_matvec_bucket(
        mats, sched, SELL_SIGMA, store=store,
        member_keys=[content_key(m) for m in mats])
    assert len(store) == len(mats)        # each member prepared once
    assert set(host["arrays"]) == set(resident["arrays"]) \
        == set(LAUNCH_FIELDS[layout])
    for k, a in host["arrays"].items():
        b = resident["arrays"][k]
        assert a.shape == b.shape and a.dtype == b.dtype, k
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=k)
    for k in ("layout", "shapes", "width", "bs", "stream_tiles"):
        assert host[k] == resident[k], k
    assert host["shapes"] == [m.shape for m in mats]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_bucket_edge_padding_is_the_launch_pad(layout):
    """``pad_container_to_bucket`` equals ``pad_arrays`` at the container's
    own edge targets, and each leaf pads by its rule: slots and cells at
    the trailing zero tile, ``cell_row`` by its last row, ``row_perm`` as
    the identity, ``slice_widths`` by 1, the rest by 0."""
    m = gen_zipf(SIZES[1], seed=70)
    container = SparseTensor.build_container(m, _schedule(layout))
    padded = pad_container_to_bucket(container)
    _, arrays = container_arrays(container)
    target = bucket_target([{k: v.shape for k, v in arrays.items()}])
    want = pad_arrays(arrays, target)
    _, got = container_arrays(padded)
    assert set(got) == set(want)
    for k in want:
        assert got[k].shape == target[k] == tuple(
            bucket_edge(n) if d == 0 or k != "blocks" else n
            for d, n in enumerate(arrays[k].shape)), k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
        region = tuple(slice(0, n) for n in arrays[k].shape)
        np.testing.assert_array_equal(got[k][region], arrays[k], err_msg=k)
    if layout == "dense":
        assert not got["dense"][arrays["dense"].shape[0]:].any()
        return
    zero = arrays["blocks"].shape[0] - 1
    assert not got["blocks"][zero:].any()
    bs = container.block_size
    assert padded.shape[0] % bs == 0 and padded.shape[1] % bs == 0
    if layout == "ell":
        n_br, mb = arrays["block_indices"].shape
        bi = got["block_indices"]
        assert (bi[n_br:] == zero).all() and (bi[:, mb:] == zero).all()
        assert not got["block_cols"][n_br:].any()
        assert not got["valid_counts"][n_br:].any()
        assert padded.shape[0] == bi.shape[0] * bs
        return
    n_cells, n_br = arrays["cell_block"].size, arrays["row_perm"].size
    assert (got["cell_block"][n_cells:] == zero).all()
    assert (got["cell_row"][n_cells:] == arrays["cell_row"][-1]).all()
    assert (np.diff(got["cell_row"]) >= 0).all()
    np.testing.assert_array_equal(got["row_perm"][n_br:],
                                  np.arange(n_br, got["row_perm"].size))
    assert (got["slice_widths"][arrays["slice_widths"].size:] == 1).all()
    assert padded.shape[0] == got["row_perm"].size * bs


@pytest.mark.parametrize("layout", LAYOUTS)
def test_device_pad_matches_host_pad(layout):
    """The same leaves padded as jax arrays stay jax arrays and equal the
    numpy padding: the resident path's stack needs no host round trip."""
    m = gen_zipf(SIZES[0], seed=80)
    _, arrays = container_arrays(
        SparseTensor.build_container(m, _schedule(layout)))
    shapes = {k: v.shape for k, v in arrays.items()}
    target = bucket_target([shapes, {k: tuple(2 * n if k != "blocks" or d == 0
                                              else n for d, n in enumerate(s))
                                     for k, s in shapes.items()}])
    on_host = pad_arrays(arrays, target)
    on_device = pad_arrays({k: jnp.asarray(v) for k, v in arrays.items()},
                           target)
    for k in target:
        assert isinstance(on_device[k], jax.Array), k
        assert on_device[k].shape == on_host[k].shape == target[k], k
        np.testing.assert_array_equal(np.asarray(on_device[k]), on_host[k],
                                      err_msg=k)


def test_a_leaf_at_its_target_is_not_copied():
    """A leaf already at its target shape comes back as the same object,
    host or device: a bucket-sized tile array is never copied again."""
    m = gen_zipf(SIZES[2], seed=90)
    c = pad_container_to_bucket(
        SparseTensor.build_container(m, _schedule("ell")))
    _, arrays = container_arrays(c)
    target = {k: v.shape for k, v in arrays.items()}
    for src in (arrays, {k: jnp.asarray(v) for k, v in arrays.items()}):
        out = pad_arrays(src, target)
        assert all(out[k] is src[k] for k in target)
    assert pad_container_to_bucket(c).blocks is c.blocks


@pytest.mark.parametrize("leaf", ["row_perm", "cell_row", "blocks"])
def test_a_leaf_past_its_target_is_refused(leaf):
    """Shapes predicted before a build (a mesh's shards) that undershoot
    the build fail loudly, whatever the leaf's fill rule."""
    a = np.zeros((5, 2, 2) if leaf == "blocks" else (5,), np.float32)
    with pytest.raises(ValueError, match="exceeds its target"):
        pad_arrays({leaf: a}, {leaf: (3,) + a.shape[1:]})
