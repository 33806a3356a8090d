from .mesh import make_test_mesh

__all__ = ["make_test_mesh"]
