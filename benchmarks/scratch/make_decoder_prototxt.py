"""Write a decoder configuration's train prototxt from the zoo builder.

Run by hand when the configuration is ADDED or resized (never by run.py):

    python benchmarks/scratch/make_decoder_prototxt.py \
        benchmarks/configs/joyai-llm-flash-l5-ep32-bf16

Reads the sizes from ``<prefix>.json`` as the job does
(``jobs/lm_decoder_solo.py zoo_kwargs``) and writes
``<prefix>.train.prototxt``: ``models.<zoo>(...)`` serialized, byte for
byte what the zoo builds.  The solver prototxt is written by hand.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def main() -> None:
    from benchmarks.harness import load_by_name
    from sparknet_tpu.proto.text_format import serialize

    job = load_by_name("jobs", "lm_decoder_solo")
    prefix = sys.argv[1]
    with open(prefix + ".json") as f:
        config = json.load(f)
    text = (f"# models.{config['zoo']}(**{job.zoo_kwargs(config)!r})\n"
            "# written by benchmarks/scratch/make_decoder_prototxt.py\n"
            + serialize(job.zoo_net(config)))
    with open(prefix + ".train.prototxt", "w") as f:
        f.write(text)
    print(f"wrote {prefix}.train.prototxt")


if __name__ == "__main__":
    main()
