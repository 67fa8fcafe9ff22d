"""The benchmark's copy of the step path trains one batch to the same loss
as the users' entry, ``launch/train.train()``, at a tiny size on the CPU:
the same corpus, the same initial state, the same plan, bucket, compiled
step and feed."""

import json

import numpy as np
import pytest

import configs
import steppath
from tiny import DATA


@pytest.mark.parametrize("mesh_shape", [(1, 1)])
def test_step_path_matches_train(mesh_shape):
    import jax

    from repro.data import sample_corpus_batch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import TrainLoopConfig, train

    cfg = json.loads((DATA / "tiny.json").read_text())
    arch = configs.program(cfg).arch(cfg)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    seed, batch, context = 3, 4, 128
    loop = TrainLoopConfig(steps=1, global_batch=batch, context=context,
                           dataset="github", seed=seed,
                           compute_dtype="bfloat16", lint="off")
    _, _, hist = train(arch, mesh, loop, log=lambda m: None)

    corpus = sample_corpus_batch("github", batch, context, arch.spec.vocab,
                                 seed=seed)
    path = steppath.StepPath(arch, mesh, None, log=lambda m: None)
    plan = path.solve([len(v) for v in corpus.values()])
    builder, step = path.get_step(plan)
    params, opt, _ = builder.init_all(jax.random.PRNGKey(seed))
    _, _, _, metrics = step(params, opt, None, path.materialize(plan, corpus))
    assert float(metrics["tokens"]) == hist[0]["tokens"]
    np.testing.assert_allclose(float(metrics["loss"]), hist[0]["loss"],
                               rtol=1e-6)
