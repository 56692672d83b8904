"""Compatibility shims — the reference's ``compat.py`` surface.

Reference: ``tensorflowonspark/compat.py`` (SURVEY.md §2 "TF1/TF2 compat
shims"): version bridges the reference needed between TF eras. The
TPU-native equivalents are mostly trivial, kept so reference-style user
code ports mechanically.
"""

from tensorflowonspark_tpu.device_info import is_tpu_available  # noqa: F401

# reference name
is_gpu_available = is_tpu_available


def export_saved_model(export_dir, apply_fn, variables, is_chief,
                       signature=None):
    """Chief-only export (reference: ``compat.export_saved_model(model,
    dir, is_chief)`` — non-chief calls are no-ops)."""
    if not is_chief:
        return
    from tensorflowonspark_tpu import export

    export.save_model(export_dir, apply_fn, variables, signature)


def disable_auto_shard(options=None):
    """No-op: the reference disabled tf.data auto-sharding for queue-fed
    datasets; our feed plane shards at the queue level by construction."""
    return options
