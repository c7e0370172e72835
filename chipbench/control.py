#!/usr/bin/env python3
"""Readings that set a cell's ``mean_gap`` limit, on the chip.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds 11,12,13

For each seed, in one process: the cell's own set-up and a short window
at its own load; then, on the requests the check samples, two sets of
gaps (the mean and the widest) below the float32 reference's best
logit:

* ``program``: of the tokens the program served (the lower reading);
* ``control``: of the token that the program's next lower precision
  puts first at each of the same positions (the upper reading). The
  control is the program's own path one step down: int8 weights
  through the Pallas kernel for a bf16 configuration, nf4 (4-bit) for
  an int8 one, its parameters quantized a layer at a time
  (:func:`lower_params`). It reads every position of the same prompts
  and served tokens in one forward pass, and does not decode.

One JSON line per seed. Benchmark runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from chipbench import harness, traffic  # noqa: E402

LOWER = {"bfloat16": "int8", "int8": "nf4"}


def lower_params(model, key):
    """The lower-precision model's parameters for the seed's weights,
    drawn and quantized a layer at a time: each layer of the stack is
    drawn as served, quantized by the program's ``Model.quantize`` as a
    one-layer stack, and the layers are joined; the leaves that are not
    stacked are drawn whole. The same parameters as
    ``model.quantize(weights.make(model, key))``, without the whole
    unquantized stack on the device at once."""
    import jax
    import jax.numpy as jnp
    from chipbench import weights
    lay = weights.layout(model)
    stacked = [p for p in lay if p.startswith(weights.STACKED + "/")]
    n_layers = lay[stacked[0]].shape[0]
    draw = jax.jit(lambda key, i: weights.layer(lay, key, i))
    layers = []
    for i in range(n_layers):
        one: dict = {}
        for path, v in draw(key, i).items():
            weights.put(one, f"{weights.STACKED}/{path}", v[None])
        layers.append(model.quantize(one)[weights.STACKED])
    rest: dict = {}
    for path, v in jax.jit(lambda key: {
            p: weights.leaf(lay, key, p)
            for p in lay if p not in stacked})(key).items():
        weights.put(rest, path, v)
    params = model.quantize(rest)
    params[weights.STACKED] = jax.tree.map(
        lambda *xs: jnp.concatenate(xs), *layers)
    return params


def control_tokens(cell, key, rows, firsts, served, on_tpu):
    """The token the lower-precision path puts first at every position
    whose served token is checked, one (positions, 1) array per row.
    Each row is padded at its end to the cell's ``buf_len``, so one
    program serves every row (attention is causal: the padding changes
    no position before it)."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    cfg = cell.arch.model_config(cell.config)
    model = build_model(cfg, LOWER[cell.config["precision"]["fmt"]],
                        use_pallas_kernels=on_tpu)
    params = lower_params(model, key)

    @jax.jit
    def first_choice(params, toks):
        h, _ = model.forward_train(params, {"tokens": toks})
        return jnp.argmax(model.logits(params, h), axis=-1)

    out = []
    width = cell.settings["buf_len"]
    for row, first, got in zip(rows, firsts, served):
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(row)] = row
        pick = np.asarray(first_choice(params, jnp.asarray(toks)))[0]
        out.append(pick[first:first + len(got)].astype(np.int32))
    del params
    gc.collect()
    return out


def one_seed(cell, seed, seconds, dev, counter):
    from repro.core.hardware import device_for_kind, get_device
    on_tpu = dev.platform == "tpu"
    spec = (device_for_kind(dev.device_kind) if on_tpu
            else get_device("tpu-v5e"))
    served = harness.build(cell, seed, on_tpu, spec)
    harness.warm_up(served, cell, seed)
    plan = traffic.generate(cell.mix, cell.settings, seed, seconds,
                            served.vocab)
    rec = harness.run_window(served, cell, plan, seconds, counter)
    layout, key, vocab = served.layout, served.key, served.vocab
    served.engine = None
    del served
    gc.collect()
    sample = harness.check_sample(rec, seed,
                                  cell.settings["check_requests"])
    rows, firsts, got, bad = harness.sample_rows(rec, sample, vocab)
    ctrl = control_tokens(cell, key, rows, firsts, got, on_tpu)
    program, control = harness.gap_stats(
        cell, layout, key, rows, firsts,
        [np.stack([g, c], axis=1) for g, c in zip(got, ctrl)])
    return {"seed": seed, "program": program, "control": control,
            "bad_tokens": bad, "tokens": int(sum(len(g) for g in got)),
            "unfinished": harness.unfinished(cell, rec)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.import_program()
    dev, _ = harness.check_device(cell.chips)
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(one_seed(cell, seed, args.seconds, dev, counter)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
