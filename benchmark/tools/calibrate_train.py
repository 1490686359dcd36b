"""Read, on the chip at the cell's own size, what the control and the
planted faults give for the numbers a ``train_fullbatch`` cell
compares (``python benchmark/tools/calibrate_train.py <config.json>
<minibatch> <seed> [<seed> ...]``).

For each seed: the data set from the seed, the rows of the train
sweep in an order drawn from the seed, then the plain reference, the
reference at bfloat16 operands (what the configuration states for the
program: a second witness), the control (operands one precision
lower, ``float8_e4m3fn``), and the reference with each fault planted:
half of each minibatch left out with the mean over the rest, and a
step that returns its state unchanged. Each is compared with the
reference by the harness's own arithmetic
(``train_fullbatch.gaps_between``), held to the configuration's
``limits``, and printed as one JSON line with ``correct`` and the
numbers that are ``over``: the witness has to read correct, the
control and each fault not. The limits in the configuration file were
set from these readings and from the program's own over a dozen seeds
(PERF.md); ``run.py --control`` holds the control to them in a run of
the cell itself.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy

    from benchmark.harness import common, data as data_lib
    from benchmark.harness import train_fullbatch

    config = common.load_json(argv[1])
    minibatch = int(argv[2])
    reference = common.load_module(config["reference"])
    sizes = config["dataset"]
    print(json.dumps({"device": jax.devices()[0].device_kind}),
          flush=True)
    for seed in (int(s) for s in argv[3:]):
        data, labels = data_lib.dataset(
            seed, config["input_shape"], sizes["n_valid"],
            sizes["n_train"], sizes["label_classes"])
        device_data, device_labels = jnp.asarray(data), jnp.asarray(labels)
        rows = sizes["n_valid"] + numpy.random.Generator(
            numpy.random.PCG64(seed)).permutation(sizes["n_train"])
        want = None
        for name, kwargs in (
                ("reference", {}),
                ("bfloat16_witness", {"operands": "bfloat16"}),
                ("control_float8_e4m3fn",
                 {"operands": "float8_e4m3fn"}),
                ("fault_half_batch", {"half_batch": True}),
                ("fault_state_unchanged", {"frozen": True})):
            t0 = time.perf_counter()
            got = reference.follow_first_epoch(
                config, seed, device_data, device_labels, rows,
                minibatch, **kwargs)
            if want is None:
                want = got
            gaps, left_out = train_fullbatch.gaps_between(got, want)
            over = sorted(key for key, value in gaps.items()
                          if value > config["limits"][key])
            print(json.dumps({
                "seed": seed, "what": name, "correct": not over,
                "over": over, "gaps": gaps, "left_out": left_out,
                "losses": [got["loss_valid0"], got["loss_train0"],
                           got["loss_valid1"]],
                "seconds": round(time.perf_counter() - t0, 1)}),
                flush=True)
        del device_data, device_labels
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
