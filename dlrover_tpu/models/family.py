"""What a family of layers says of itself, once, to the layers above.

A family is the layers of one kind that sow statistics and choose kernels:
the expert layers (``models/moe.py``), the delta-rule mixers
(``models/linear_attention.py``), the state-space mixers
(``models/mamba2.py``), the gated short convolutions
(``models/gated_conv.py``), the softmax attentions of a model with windowed
layers (``models/attention.py``) and the multi-token-prediction module
(``models/transformer.py``).  Each module declares ONE :class:`Family`
beside its ``STATS_NAME``; ``models/transformer.py`` lists them
(``FAMILIES``).  Four places read a declaration and none of them knows a
vector's layout or a planner's arguments: the step program folds the sown
vectors (``trainer/train_lib.py``), the trainer reads the folded vector on
the report cadence into the family's event and asks ``kernel_facts`` for
the ``compile`` event (``trainer/elastic_trainer.py``), and the master
keeps and renders the event's attributes by its row of
``master/speed_monitor.HEALTH_KINDS``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional


class Family(NamedTuple):
    # The telemetry event a report books the family under.
    event: str
    # The step metrics a report reads, in the order ``read`` takes them.
    # The first names the ``host_block`` of the one fetch.  Each comes with
    # the fold of the layers' sown ``[n, width]`` vectors into the one the
    # step hands out, which is also the fold over microbatches; ``None``
    # for a scalar the step computes itself, which reaches the host with
    # the loss.
    stats: Mapping[str, Optional[Callable]]
    # Whether (how many layers of) a configuration has the family.
    has: Callable[[Any], Any]
    # (config, the fetched values) -> the event's attributes, the layers'
    # static geometry included.
    read: Callable[..., Dict[str, Any]]
    # (config, sequence length) -> the family's keys of the ``compile``
    # event, every key whether or not the configuration has the family:
    # asked of the function the layer asks, with the layer's own fields.
    kernel_facts: Callable[[Any, int], Dict[str, Any]]
    # The attribute that feeds the numeric monitor's ``state_absmax``.
    absmax: Optional[str] = None
