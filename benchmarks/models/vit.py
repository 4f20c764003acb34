"""A Vision Transformer of a configuration's published sizes, registered
with the program's model registry under the configuration's model name.

The program's registry names ViT-B/16 and a toy only; a user with another
published size registers it as the program documents (``models/registry.py
register``) and builds it with the program's own ``models/vit.py build_vit``.
That is all this does: the blocks, the attention dispatch, the engine and
the topology are the program's. A later PR that adds the name to the
program's registry makes this file a no-op (the name is then there already).
"""


def register(config: dict) -> None:
    from storm_tpu.models import registry
    from storm_tpu.models.vit import build_vit

    name, sizes = config["model"]["name"], config["published"]
    if name in registry.registry_names():
        return

    @registry.register(name)
    def build(num_classes: int = sizes["num_labels"],
              input_shape: tuple = (sizes["image_size"], sizes["image_size"],
                                    sizes["num_channels"])):
        return build_vit(
            name, num_classes, tuple(input_shape),
            patch=sizes["patch_size"], dim=sizes["hidden_size"],
            depth=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            mlp_dim=sizes["intermediate_size"])
