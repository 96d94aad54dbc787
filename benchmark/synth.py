"""Seeded inputs: learnable batches made on the device, and a packed JPEG
set for the imgbin chain.  One general generator each; a traffic file only
sets their parameters."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)), '.cache')


def learnable_batches(seed: int, n_batches: int, batch: int, shape,
                      num_classes: int, dtype, p: dict, devices):
    """``n_batches`` host batches ``(data NCHW, label (batch, 1))`` on which
    a classifier can learn: labels from ``p['classes']`` seeded classes out
    of ``num_classes``, image = that class's low-frequency template plus
    noise, centred on zero like the augment chain's mean-subtracted output.

    Made on ``devices`` (the batch split over them, so that no chip holds
    more than its share) in one jitted call a batch, in the type the
    trainer ships, and fetched: ``stage_batch`` takes host arrays only.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    c, y, x = shape
    n_cls, grid = int(p['classes']), int(p['template_grid'])
    amp, noise = float(p['template_amplitude']), float(p['noise_std'])
    mesh = Mesh(np.asarray(devices), ('d',))
    rows = NamedSharding(mesh, P('d'))

    def make(seed, i):
        # the seed is an argument, not a constant in the program: a new
        # seed must find the compiled program in the cache.  Classes and
        # templates hang on the seed alone, labels and noise on the batch
        k_ids, k_tmpl, k_rest = jax.random.split(jax.random.PRNGKey(seed), 3)
        k_lab, k_noise = jax.random.split(jax.random.fold_in(k_rest, i))
        ids = jax.random.permutation(k_ids, num_classes)[:n_cls]
        small = jax.random.normal(k_tmpl, (n_cls, c, grid, grid))
        which = jax.random.randint(k_lab, (batch,), 0, n_cls)
        img = (amp * jax.image.resize(small[which], (batch, c, y, x),
                                      'bilinear')
               + noise * jax.random.normal(k_noise, (batch, c, y, x)))
        return img.astype(dtype), ids[which].astype(jnp.float32)[:, None]

    make = jax.jit(make, out_shardings=(rows, rows))
    out = []
    for i in range(n_batches):
        data, label = make(np.int32(seed), np.int32(i))
        out.append((np.asarray(data), np.asarray(label)))
    return out


def packed_jpegs(p: dict) -> dict:
    """A packed imgbin set for the ``imgbin`` chain: ``p['distinct_images']``
    seeded low-frequency ``p['image_size']``-square JPEGs (photo-like decode
    cost), listed ``p['images']`` times in a seeded order with seeded labels
    and packed by the program's own ``tools/im2bin.py``.

    Built once per checkout under ``.cache/`` and found again by a key made
    of the parameters and of this file: building it (and the chain's first
    pass for the mean image, whose file lies beside it) takes longer than a
    run measures, and a set that changed with ``--seed`` would be built in
    every run.  ``--seed`` drives the chain's crops and mirrors instead.
    Returns the paths of the list, the bin and the (maybe absent) mean."""
    from PIL import Image
    with open(os.path.abspath(__file__), 'rb') as f:
        key = hashlib.sha256(json.dumps(p, sort_keys=True).encode()
                             + f.read()).hexdigest()[:16]
    home = os.path.join(CACHE, 'imgbin', key)
    paths = {'list': os.path.join(home, 'train.lst'),
             'bin': os.path.join(home, 'train.bin'),
             'mean': os.path.join(home, 'mean.bin')}
    if os.path.exists(os.path.join(home, 'done')):
        return paths
    shutil.rmtree(home, ignore_errors=True)
    jpegs = os.path.join(home, 'jpeg')
    os.makedirs(jpegs)
    rng = np.random.RandomState(int(p['dataset_seed']))
    size, n_distinct = int(p['image_size']), int(p['distinct_images'])
    for i in range(n_distinct):
        small = rng.randint(0, 255, (16, 16, 3), dtype=np.uint8)
        Image.fromarray(small).resize((size, size), Image.BILINEAR).save(
            os.path.join(jpegs, f'{i}.jpg'), quality=int(p['jpeg_quality']))
    with open(paths['list'], 'w') as f:
        for i in range(int(p['images'])):
            f.write(f'{i}\t{int(rng.randint(0, int(p["num_classes"])))}\t'
                    f'{int(rng.randint(0, n_distinct))}.jpg\n')
    subprocess.run([sys.executable, os.path.join(ROOT, 'tools', 'im2bin.py'),
                    paths['list'], jpegs, paths['bin']], check=True,
                   stdout=subprocess.DEVNULL)
    shutil.rmtree(jpegs)
    with open(os.path.join(home, 'done'), 'w') as f:
        f.write(key + '\n')
    return paths
