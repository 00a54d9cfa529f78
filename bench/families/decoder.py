"""The dense decoder: attention and a dense FFN in every layer, Monarch or
dense linears, an untied unembedding (CodeQwen1.5, Nemotron-4)."""

from bench.model import _weight_tree as weight_tree
from bench.parts import step_parts
from bench.reference import score
from bench.work import shape_of, step_work

# the program's named scopes in the mixed step (serving/tracing.py)
SCOPES = ("linear", "attention", "unembed", "embed", "norm", "sample")

__all__ = ["SCOPES", "score", "shape_of", "step_parts", "step_work",
           "weight_tree"]
