"""Offline fp32 reconstruction from a (sharded) checkpoint directory.

Reference: ``deepspeed/utils/zero_to_fp32.py`` — stitches per-rank zero
shard files back into a consolidated fp32 state dict, offline.  Here
checkpoints are Orbax/tensorstore directories whose array storage is
already logically whole (shards are an Orbax storage detail), so
"reconstruction" is a host-side restore of the ``params`` subtree; no
per-rank shard walking is needed, and any (dp, tp, pp) topology change
between save and load is absorbed by restore-time sharding (the
universal-checkpoint property, reference ``deepspeed/checkpoint/``).

CLI:  python -m deepspeed_tpu.checkpoint.zero_to_fp32 <ckpt_dir> <out.npz>
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, Optional

import numpy as np

from .engine import LATEST_FILE


def get_fp32_state_dict_from_zero_checkpoint(
        ckpt_dir: str, tag: Optional[str] = None) -> Dict[str, Any]:
    """Load the consolidated fp32 param tree from a checkpoint dir on
    host memory (no engine, no mesh required)."""
    import orbax.checkpoint as ocp

    if tag is None:
        latest = os.path.join(ckpt_dir, LATEST_FILE)
        if not os.path.exists(latest):
            raise FileNotFoundError(
                f"no tag given and no '{LATEST_FILE}' file in {ckpt_dir}")
        with open(latest) as f:
            tag = f.read().strip()
    path = os.path.abspath(os.path.join(ckpt_dir, tag, "state"))
    if not os.path.isdir(path):
        raise FileNotFoundError(f"checkpoint state dir not found: {path}")
    ckptr = ocp.Checkpointer(ocp.StandardCheckpointHandler())
    state = ckptr.restore(path)
    params = state["params"] if isinstance(state, dict) else state.params
    return _tree_to_host_fp32(params)


def _tree_to_host_fp32(tree: Any) -> Any:
    import jax
    return jax.tree.map(
        lambda x: np.asarray(x, dtype=np.float32), tree)


def _key_of(entry) -> str:
    """Uniform rendering of one pytree path entry: DictKey('a'),
    GetAttrKey('count') (namedtuple field) and SequenceKey(0) all become
    bare names, so a namedtuple and the dict Orbax restores it as produce
    the same flat key."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def flatten_state_dict(tree: Any, prefix: str = "",
                       sep: str = ".") -> Dict[str, np.ndarray]:
    """Any pytree -> flat {'a.b.c': array} (torch-state-dict style keys;
    ``sep='/'`` gives the universal-checkpoint atom key scheme)."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out: Dict[str, np.ndarray] = {}
    for path, leaf in flat:
        out[prefix + sep.join(_key_of(p) for p in path)] = np.asarray(leaf)
    return out


def convert_zero_checkpoint_to_fp32_state_dict(
        ckpt_dir: str, output_file: str, tag: Optional[str] = None) -> None:
    params = get_fp32_state_dict_from_zero_checkpoint(ckpt_dir, tag)
    flat = flatten_state_dict(params)
    np.savez(output_file, **flat)
    total = sum(v.size for v in flat.values())
    print(f"saved {len(flat)} tensors / {total:,} params -> {output_file}")


def main(argv=None):
    # Host-side reconstruction needs no accelerator: pin the CPU platform
    # BEFORE any backend init so the CLI never claims (or blocks on) a
    # chip that a training process holds.
    import jax
    jax.config.update("jax_platforms", "cpu")
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) not in (2, 3):
        print("usage: python -m deepspeed_tpu.checkpoint.zero_to_fp32 "
              "<checkpoint_dir> <output.npz> [tag]")
        return 1
    convert_zero_checkpoint_to_fp32_state_dict(
        argv[0], argv[1], argv[2] if len(argv) == 3 else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
