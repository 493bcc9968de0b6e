"""Data substrate: deterministic shard-aware synthetic pipelines and the
update-stream generators that drive the engine (tests, apps, the serve
and train launchers)."""

from .pipeline import TokenPipeline, make_batch_specs, synth_batch
from .updates import (LabeledStream, LabeledUpdate, RowLocalStream,
                      UpdateStream, labeled_stream, row_local_stream,
                      zipf_row_stream)

__all__ = ["TokenPipeline", "make_batch_specs", "synth_batch",
           "UpdateStream", "RowLocalStream", "row_local_stream",
           "zipf_row_stream", "LabeledStream", "LabeledUpdate",
           "labeled_stream"]
