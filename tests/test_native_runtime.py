"""Native C++ runtime tests: page reader + libjpeg decode vs Python refs."""

import io
import os
import subprocess

import numpy as np
import pytest

from cxxnet_tpu.runtime.native import (NativePageReader, decode_jpeg,
                                       native_available)
from cxxnet_tpu.utils.io_stream import BinaryPage

pytestmark = pytest.mark.skipif(not native_available(),
                                reason='native runtime not built')


def make_bin(tmp_path, pages):
    path = tmp_path / 'x.bin'
    with open(path, 'wb') as f:
        for blobs in pages:
            page = BinaryPage()
            for b in blobs:
                assert page.push(b)
            page.save(f)
    return str(path)


def test_native_page_reader_matches_python(tmp_path):
    pages = [[b'a', b'bb' * 100, b''], [os.urandom(5000)]]
    path = make_bin(tmp_path, pages)
    reader = NativePageReader(path)
    got = list(reader.iter_pages())
    reader.close()
    assert got == pages


def test_native_jpeg_decode_matches_pil(tmp_path):
    from PIL import Image
    rng = np.random.RandomState(0)
    arr = rng.randint(0, 255, (32, 48, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format='JPEG', quality=95)
    blob = buf.getvalue()
    native = decode_jpeg(blob)
    assert native is not None and native.shape == (32, 48, 3)
    with Image.open(io.BytesIO(blob)) as im:
        pil = np.asarray(im.convert('RGB'))
    # both use libjpeg; allow minor IDCT implementation differences
    assert np.mean(np.abs(native.astype(int) - pil.astype(int))) < 2.0


def test_native_decode_rejects_garbage():
    assert decode_jpeg(b'not a jpeg at all') is None


def test_imgbin_iterator_uses_native_jpeg(tmp_path):
    from PIL import Image
    from cxxnet_tpu.io.data import create_iterator
    rng = np.random.RandomState(1)
    lst = tmp_path / 'a.lst'
    page = BinaryPage()
    with open(lst, 'w') as f:
        for i in range(6):
            arr = rng.randint(0, 255, (20, 20, 3)).astype(np.uint8)
            buf = io.BytesIO()
            Image.fromarray(arr).save(buf, format='JPEG', quality=95)
            assert page.push(buf.getvalue())
            f.write(f'{i}\t{i % 3}\tim{i}.jpg\n')
    with open(tmp_path / 'a.bin', 'wb') as f:
        page.save(f)
    cfg = [('iter', 'imgbin'), ('image_list', str(lst)),
           ('image_bin', str(tmp_path / 'a.bin')),
           ('input_shape', '3,20,20'), ('batch_size', '3'), ('silent', '1')]
    it = create_iterator(cfg)
    it.init()
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data.shape == (3, 3, 20, 20)


def test_native_ordered_page_reader(tmp_path):
    """cxr_open_order reads pages by index with seeks — arbitrary order,
    repeats included (the imgbinx shuffled-epoch access pattern)."""
    pages = [[b'page0-a', b'page0-b'], [b'page1-a'], [b'page2-a', b'x' * 999]]
    path = make_bin(tmp_path, pages)
    order = [2, 0, 1, 0]
    reader = NativePageReader(path, order=order)
    got = list(reader.iter_pages())
    reader.close()
    assert got == [pages[i] for i in order]


def test_native_ordered_reader_edge_cases(tmp_path):
    pages = [[b'p0'], [b'p1']]
    path = make_bin(tmp_path, pages)
    # empty order reads NOTHING (sharded worker owning no pages)
    reader = NativePageReader(path, order=[])
    assert list(reader.iter_pages()) == []
    reader.close()
    # an index past EOF is an error, not silent truncation
    reader = NativePageReader(path, order=[0, 7])
    with pytest.raises(RuntimeError, match='truncated'):
        list(reader.iter_pages())
    reader.close()
