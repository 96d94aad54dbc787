"""IO pipeline tests: BinaryPage format, iterator chains, augmentation."""

import gzip
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

from cxxnet_tpu.io.data import create_iterator
from cxxnet_tpu.utils.io_stream import BinaryPage


def write_mnist(tmpdir, n=50, rows=8, cols=8, seed=0):
    rng = np.random.RandomState(seed)
    img = rng.randint(0, 255, (n, rows, cols)).astype(np.uint8)
    y = rng.randint(0, 3, n).astype(np.uint8)
    pi = os.path.join(tmpdir, 'img.gz')
    pl = os.path.join(tmpdir, 'lbl.gz')
    with gzip.open(pi, 'wb') as f:
        f.write(struct.pack('>iiii', 2051, n, rows, cols))
        f.write(img.tobytes())
    with gzip.open(pl, 'wb') as f:
        f.write(struct.pack('>ii', 2049, n))
        f.write(y.tobytes())
    return pi, pl, img, y


def test_binary_page_roundtrip(tmp_path):
    page = BinaryPage()
    blobs = [b'hello', b'x' * 1000, b'', b'last']
    for b in blobs:
        assert page.push(b)
    path = tmp_path / 'page.bin'
    with open(path, 'wb') as f:
        page.save(f)
    assert path.stat().st_size == BinaryPage.N_BYTES
    page2 = BinaryPage()
    with open(path, 'rb') as f:
        assert page2.load(f)
        assert not BinaryPage().load(f)   # EOF
    assert list(page2) == blobs


def test_mnist_iterator_chain(tmp_path):
    pi, pl, img, y = write_mnist(str(tmp_path))
    cfg = [('iter', 'mnist'), ('path_img', pi), ('path_label', pl),
           ('input_flat', '1'), ('iter', 'threadbuffer'),
           ('batch_size', '16'), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    batches = list(it)
    # 50 // 16; the mnist source itself drops the tail remainder exactly
    # like the reference (iter_mnist-inl.hpp:63)
    assert len(batches) == 3
    assert batches[0].data.shape == (16, 1, 1, 64)
    np.testing.assert_allclose(batches[0].data[0].ravel(),
                               img[0].ravel() / 256.0, rtol=1e-6)
    assert batches[0].label[0, 0] == y[0]
    # second epoch identical (no per-epoch reshuffle when shuffle=0)
    batches2 = list(it)
    np.testing.assert_array_equal(batches[1].data, batches2[1].data)


def test_tail_batch_emitted_with_padd(tmp_path):
    """round_batch=0 through the batch adapter keeps the short final batch,
    padded to full size with num_batch_padd = batch_size - top
    (iter_batch_proc-inl.hpp:101-103) — no instance is silently dropped."""
    lst = make_img_dataset(str(tmp_path), n=10)
    cfg = [('iter', 'img'), ('image_list', lst),
           ('image_root', str(tmp_path)),
           ('input_shape', '3,20,20'), ('batch_size', '4'),
           ('round_batch', '0'), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    batches = list(it)
    assert [b.num_batch_padd for b in batches] == [0, 0, 2]
    # every batch keeps the full static shape (jit-friendly)
    assert all(b.data.shape[0] == 4 for b in batches)
    # all 10 instances appear exactly once among the non-pad rows
    seen = np.concatenate([b.inst_index[:4 - b.num_batch_padd]
                           for b in batches])
    assert sorted(seen.tolist()) == list(range(10))


def _write_png(path, arr):
    from PIL import Image
    Image.fromarray(arr).save(path)


def make_img_dataset(tmpdir, n=12, size=20):
    rng = np.random.RandomState(1)
    lst = os.path.join(tmpdir, 'a.lst')
    with open(lst, 'w') as f:
        for i in range(n):
            arr = rng.randint(0, 255, (size, size, 3)).astype(np.uint8)
            fname = f'im{i}.png'
            _write_png(os.path.join(tmpdir, fname), arr)
            f.write(f'{i}\t{i % 3}\t{fname}\n')
    return lst


def test_img_iterator_with_crop_and_batch(tmp_path):
    lst = make_img_dataset(str(tmp_path))
    cfg = [('iter', 'img'), ('image_list', lst),
           ('image_root', str(tmp_path)),
           ('input_shape', '3,16,16'), ('batch_size', '4'),
           ('rand_crop', '1'), ('rand_mirror', '1'), ('silent', '1'),
           ('round_batch', '1'), ('iter', 'end')]
    it = create_iterator(cfg)
    it.init()
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data.shape == (4, 3, 16, 16)
    assert batches[0].label.shape == (4, 1)


def test_img_round_batch_pads_with_next_epoch(tmp_path):
    lst = make_img_dataset(str(tmp_path), n=10)
    cfg = [('iter', 'img'), ('image_list', lst),
           ('image_root', str(tmp_path)),
           ('input_shape', '3,20,20'), ('batch_size', '4'),
           ('round_batch', '1'), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    batches = list(it)
    assert len(batches) == 3
    assert batches[2].num_batch_padd == 2
    # padded tail contains wrapped instances 0,1
    assert list(batches[2].inst_index) == [8, 9, 0, 1]


def test_imgbin_roundtrip_via_im2bin(tmp_path):
    lst = make_img_dataset(str(tmp_path), n=8)
    out_bin = str(tmp_path / 'a.bin')
    root = str(tmp_path)
    tool = os.path.join(os.path.dirname(__file__), '..', 'tools', 'im2bin.py')
    subprocess.check_call([sys.executable, tool, lst, root, out_bin])
    cfg = [('iter', 'imgbin'), ('image_list', lst), ('image_bin', out_bin),
           ('input_shape', '3,20,20'), ('batch_size', '4'), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data.shape == (4, 3, 20, 20)
    # decode matches the original pixels (png is lossless)
    from PIL import Image
    ref = np.asarray(Image.open(tmp_path / 'im0.png').convert('RGB'),
                     np.float32).transpose(2, 0, 1)
    np.testing.assert_array_equal(batches[0].data[0], ref)


def test_mean_image_created_and_cached(tmp_path, capsys):
    lst = make_img_dataset(str(tmp_path), n=6)
    mean_path = str(tmp_path / 'mean.bin')
    cfg = [('iter', 'img'), ('image_list', lst),
           ('image_root', str(tmp_path)),
           ('input_shape', '3,20,20'), ('batch_size', '2'),
           ('image_mean', mean_path), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    assert os.path.exists(mean_path)
    b1 = list(it)[0]
    # reloading uses cached mean
    it2 = create_iterator(cfg)
    it2.init()
    b2 = list(it2)[0]
    np.testing.assert_allclose(b1.data, b2.data, rtol=1e-5)
    # mean-subtracted data should be roughly centered
    assert abs(b1.data.mean()) < 30


def test_augment_affine_rotation_180(tmp_path):
    # rotate=180 flips the image both ways; content preserved
    from cxxnet_tpu.io.iter_augment import ImageAugmenter
    rng = np.random.RandomState(0)
    img = np.zeros((3, 11, 11), np.float32)
    img[:, 2, 3] = 100.0
    aug = ImageAugmenter()
    aug.set_param('rotate', '180')
    aug.set_param('fill_value', '0')
    out = aug.process(img, rng, 11, 11)
    assert out.shape[1] >= 11
    # bright pixel moves to (9,8): 180° about the reference's size/2 center
    pos = np.unravel_index(np.argmax(out[0]), out[0].shape)
    assert pos == (9, 8), pos


def test_threadbuffer_slow_consumer_terminates():
    """Regression: producer finishing against a full queue must still
    deliver the stop sentinel (a slow consumer previously hung forever)."""
    import time as _time
    from cxxnet_tpu.utils.thread_buffer import ThreadBuffer
    buf = ThreadBuffer(lambda: iter([1, 2, 3]), buffer_size=1)
    got = []
    for item in buf:
        _time.sleep(0.3)     # let the producer finish while the queue is full
        got.append(item)
    assert got == [1, 2, 3]


def test_native_im2bin_matches_python_tool(tmp_path):
    """runtime/im2bin output must be byte-identical to tools/im2bin.py,
    for both tab- and space-separated .lst files."""
    root_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    native_tool = os.path.join(root_dir, 'runtime', 'im2bin')
    if not os.path.exists(native_tool):
        pytest.skip('runtime/im2bin not built')
    py_tool = os.path.join(root_dir, 'tools', 'im2bin.py')
    lst = make_img_dataset(str(tmp_path), n=8)
    # space-separated variant of the same list
    lst_sp = str(tmp_path / 'space.lst')
    with open(lst) as f, open(lst_sp, 'w') as g:
        g.write(f.read().replace('\t', ' '))
    for lst_file, tag in ((lst, 'tab'), (lst_sp, 'sp')):
        py_bin = str(tmp_path / f'py_{tag}.bin')
        nat_bin = str(tmp_path / f'nat_{tag}.bin')
        subprocess.check_call([sys.executable, py_tool, lst_file,
                               str(tmp_path), py_bin])
        subprocess.check_call([native_tool, lst_file, str(tmp_path), nat_bin])
        with open(py_bin, 'rb') as a, open(nat_bin, 'rb') as b:
            assert a.read() == b.read()


# --- imgbinx: two-stage shuffled pipeline --------------------------------

def _encode_png(arr):
    import io as _io
    from PIL import Image
    buf = _io.BytesIO()
    Image.fromarray(arr).save(buf, format='PNG')
    return buf.getvalue()


def _write_bin_dataset(tmpdir, n, size=6):
    """Write a .bin/.lst pair in-process (page size may be monkeypatched
    by the caller) and return (lst, bin) paths."""
    rng = np.random.RandomState(7)
    lst = os.path.join(tmpdir, 'd.lst')
    binp = os.path.join(tmpdir, 'd.bin')
    page = BinaryPage()
    with open(binp, 'wb') as fb, open(lst, 'w') as fl:
        for i in range(n):
            arr = rng.randint(0, 255, (size, size, 3)).astype(np.uint8)
            blob = _encode_png(arr)
            if not page.push(blob):
                page.save(fb)
                page.clear()
                assert page.push(blob)
            fl.write(f'{i}\t{i % 5}\t x\n')
        if page.size:
            page.save(fb)
    return lst, binp


def _instance_order(cfg):
    it = create_iterator(cfg)
    it.init()
    return [int(i) for b in it
            for i in b.inst_index[:b.batch_size - b.num_batch_padd]]


@pytest.fixture
def small_pages(monkeypatch):
    """Shrink BinaryPage to 2KB so multi-page datasets are test-sized;
    disable the native reader (its page size is the real 64MB)."""
    monkeypatch.setattr(BinaryPage, 'K_PAGE_SIZE', 512)
    monkeypatch.setattr(BinaryPage, 'N_BYTES', 512 * 4)
    from cxxnet_tpu.runtime import native
    monkeypatch.setattr(native, 'native_available', lambda: False)


def test_imgbinx_matches_imgbin_when_unshuffled(tmp_path, small_pages):
    lst, binp = _write_bin_dataset(str(tmp_path), n=24)
    base = [('image_list', lst), ('image_bin', binp),
            ('input_shape', '3,6,6'), ('batch_size', '4'), ('silent', '1')]
    a = _instance_order([('iter', 'imgbin')] + base)
    b = _instance_order([('iter', 'imgbinx')] + base)
    assert a == list(range(24))
    assert b == a


def test_imgbinx_shuffles_pages_and_instances(tmp_path, small_pages):
    """shuffle=1 randomizes page order AND within-page instance order
    (iter_thread_imbin_x-inl.hpp:195-197,316-318); every instance appears
    exactly once; epochs continue the RNG stream (different orders)."""
    lst, binp = _write_bin_dataset(str(tmp_path), n=30)
    from cxxnet_tpu.io.iter_imbin import scan_page_table
    counts = scan_page_table(binp)
    assert len(counts) >= 3, 'dataset must span multiple pages'
    cfg = [('iter', 'imgbinx'), ('image_list', lst), ('image_bin', binp),
           ('input_shape', '3,6,6'), ('batch_size', '5'),
           ('shuffle', '1'), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    flat = lambda batches: [int(i) for b in batches for i in b.inst_index]
    e1 = flat(it)
    e2 = flat(it)
    assert sorted(e1) == list(range(30))
    assert sorted(e2) == list(range(30))
    assert e1 != list(range(30)), 'shuffle produced identity order'
    assert e1 != e2, 'epochs replayed the same permutation'
    # within-page shuffle: some page's instances are not consecutive-sorted
    starts = np.concatenate([[0], np.cumsum(counts)]).astype(int)
    page_of = np.zeros(30, int)
    for p in range(len(counts)):
        page_of[starts[p]:starts[p + 1]] = p
    runs = [list(g) for g in np.split(np.asarray(e1),
            np.where(np.diff(page_of[e1]) != 0)[0] + 1)]
    assert any(r != sorted(r) for r in runs), 'within-page order untouched'


def test_imgbin_single_file_shuffle_randomizes_pages(tmp_path, small_pages):
    """Plain imgbin shuffle=1 on a single multi-page .bin shuffles page
    order (fix for the round-2 no-op); labels stay paired."""
    lst, binp = _write_bin_dataset(str(tmp_path), n=30)
    cfg = [('iter', 'imgbin'), ('image_list', lst), ('image_bin', binp),
           ('input_shape', '3,6,6'), ('batch_size', '5'),
           ('shuffle', '1'), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    orders, batches = [], []
    for _ in range(3):   # page permutations continue the RNG stream
        epoch = list(it)
        batches += epoch
        orders.append([int(i) for b in epoch for i in b.inst_index])
    assert all(sorted(o) == list(range(30)) for o in orders)
    assert any(o != list(range(30)) for o in orders), 'page shuffle no-op'
    labels = {int(i): float(l[0]) for b in batches
              for i, l in zip(b.inst_index, b.label)}
    assert all(labels[i] == i % 5 for i in range(30)), 'labels unpaired'


@pytest.mark.slow
def test_io_throughput_imgbin_vs_imgbinx(tmp_path):
    """The decoupled imgbinx decode stage should not be slower than plain
    imgbin on the same data (test_io-style pump; both complete, rates
    printed for the record)."""
    import time
    lst = make_img_dataset(str(tmp_path), n=64, size=32)
    out_bin = str(tmp_path / 'a.bin')
    tool = os.path.join(os.path.dirname(__file__), '..', 'tools', 'im2bin.py')
    subprocess.check_call([sys.executable, tool, lst, str(tmp_path), out_bin])
    rates = {}
    for kind in ('imgbin', 'imgbinx'):
        cfg = [('iter', kind), ('image_list', lst), ('image_bin', out_bin),
               ('input_shape', '3,32,32'), ('batch_size', '8'),
               ('shuffle', '1'), ('silent', '1')]
        it = create_iterator(cfg)
        it.init()
        t0 = time.perf_counter()
        cnt = sum(b.batch_size - b.num_batch_padd
                  for ep in range(2) for b in it)
        rates[kind] = cnt / (time.perf_counter() - t0)
        assert cnt == 128
    print(f'test_io throughput inst/s: {rates}')
    assert rates['imgbinx'] > 0.3 * rates['imgbin']


def test_imgbin_worker_sharding_partitions_pages(tmp_path, small_pages):
    """dist_num_worker=N on a single file: workers own disjoint pages
    covering the whole dataset, shuffled or not (the sharded paths seek
    only owned pages)."""
    lst, binp = _write_bin_dataset(str(tmp_path), n=30)
    for shuffle in ('0', '1'):
        per_worker = []
        for rank in (0, 1):
            cfg = [('iter', 'imgbin'), ('image_list', lst),
                   ('image_bin', binp), ('input_shape', '3,6,6'),
                   ('batch_size', '1'), ('shuffle', shuffle),
                   ('dist_num_worker', '2'), ('dist_worker_rank', str(rank)),
                   ('silent', '1')]
            it = create_iterator(cfg)
            it.init()
            per_worker.append({int(i) for b in it for i in b.inst_index})
        assert per_worker[0].isdisjoint(per_worker[1]), shuffle
        assert per_worker[0] | per_worker[1] == set(range(30)), shuffle


def test_membuffer_caches_and_loops(tmp_path):
    """membuffer caches the first max_nbatch batches and replays them
    every epoch (iter_mem_buffer-inl.hpp:16-75)."""
    pi, pl, img, y = write_mnist(str(tmp_path), n=64)
    cfg = [('iter', 'mnist'), ('path_img', pi), ('path_label', pl),
           ('input_flat', '1'), ('batch_size', '16'),
           ('iter', 'membuffer'), ('max_nbatch', '2'), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    e1 = list(it)
    e2 = list(it)
    assert len(e1) == 2 and len(e2) == 2     # capped at max_nbatch
    for a, b in zip(e1, e2):
        np.testing.assert_array_equal(a.data, b.data)


def test_imgbinx_decode_pool_order_identical(tmp_path, small_pages):
    """The decode thread pool must yield the exact instance stream of the
    serial path for any thread count (order-preserving submission
    window) — shuffle permutations included."""
    lst, binp = _write_bin_dataset(str(tmp_path), 37)

    def stream(threads):
        cfg = [('iter', 'imgbinx'), ('image_list', lst),
               ('image_bin', binp), ('shuffle', '1'),
               ('decode_threads', str(threads)), ('silent', '1'),
               ('seed_data', '5'), ('batch_size', '8'),
               ('input_shape', '3,6,6'), ('round_batch', '0')]
        return _instance_order(cfg)

    base = stream(1)
    assert sorted(base) == list(range(37))
    for t in (3, 8):
        assert stream(t) == base, f'decode_threads={t} changed the stream'


def test_binary_page_property_roundtrip():
    """Property test: any blob sequence (incl. empty blobs and an
    exact-fit final blob) survives push -> save -> load -> iterate with
    order and bytes intact, and a full page refuses further pushes —
    the bit-compatibility contract behind imgbin interop
    (src/utils/io.h:253-326)."""
    import io as _io

    from hypothesis import given, settings, strategies as st

    @settings(max_examples=12, deadline=None)
    @given(st.lists(st.binary(min_size=0, max_size=4096), max_size=40),
           st.booleans())
    def run(blobs, exact_fill):
        page = BinaryPage()
        pushed = []
        for b in blobs:
            if page.push(b):
                pushed.append(b)
        if exact_fill and page._free_bytes() >= 4:
            fill = b'z' * (page._free_bytes() - 4)
            assert page.push(fill)
            pushed.append(fill)
            assert page._free_bytes() == 0
            assert not page.push(b'')   # even b'' needs a 4-byte header
        buf = _io.BytesIO()
        page.save(buf)
        assert buf.tell() == BinaryPage.N_BYTES
        buf.seek(0)
        p2 = BinaryPage()
        assert p2.load(buf)
        assert list(p2) == pushed

    run()
